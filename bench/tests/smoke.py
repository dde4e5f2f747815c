"""Small stand-ins of the benchmark's serving cell for CPU tests: the
same files and code, qwen2's widths at two layers and a small
vocabulary, short prompts and outputs."""

from __future__ import annotations

import dataclasses

from bench import spec

# serving at qwen2's published widths, two layers and a small vocabulary:
# the check's limit is in logits, whose spread follows the width
SERVE_WIDTHS = dict(num_hidden_layers=2, vocab_size=509)
# outputs long enough that the check judges some tens of tokens, as the
# int4 control's widest gap grows with the tokens judged
SMALL_SERVE = dict(prompt_buckets=[8, 16], prompt_median=11,
                   prompt_sigma=0.5, gen_median=10, gen_sigma=0.5, gen_min=2,
                   gen_max=24, max_len=48, page_size=8, max_batch=2,
                   rate_per_s=4.0, check_requests=4)


_load_cell = spec.load_cell


def small_cell(name: str, root=spec.ROOT):
    """The cell of BENCHMARK.json, shrunk."""
    c = _load_cell(name, root)
    return dataclasses.replace(c, config=dict(c.config, **SERVE_WIDTHS),
                               traffic=dict(c.traffic, **SMALL_SERVE))


def patch_run(monkeypatch, run_mod, trace_mod=None):
    """Run ``bench/run.py`` here: no look for a chip, no compile cache
    or environment written, CPU peaks, the cells shrunk, and (for traced
    runs) the CPU client's threads read as the device."""
    monkeypatch.setattr(run_mod, "check_platform", lambda d, c: None)
    monkeypatch.setattr(run_mod, "_setup_env", lambda: None)
    monkeypatch.setattr(run_mod, "_enable_cache", lambda: "off")
    monkeypatch.setattr(spec, "load_cell", small_cell)
    monkeypatch.setattr(spec, "peaks_for", lambda kind: {
        "int8_ops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    if trace_mod is not None:
        monkeypatch.setattr(trace_mod, "DEVICE_PREFIX", "/host:CPU")
        monkeypatch.setattr(trace_mod, "OP_LINES", ("tf_XLAPjRtCpuClient",))
