"""A serving cell's run with a token altered where it is produced, or the
int4 control in the program's place: ``correct`` comes out false under
the cell's own limits (CPU, tiny size, the look for a chip bypassed)."""

import dataclasses
import json

import jax.numpy as jnp

from bench import run, serve_cell
from bench.tests import smoke

CELL = "qwen2-0.5b.serve.chat-sat"


def _correct(monkeypatch, capsys) -> bool:
    smoke.patch_run(monkeypatch, run)
    assert run.main(["--workload", CELL, "--seed", "515151", "--seconds",
                     "1.5", "--trace", "0"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])[
        "correct"]


def test_altered_token_is_not_correct(monkeypatch, capsys):
    real = serve_cell.make_engine

    def make(*a, **kw):
        eng = real(*a, **kw)
        decode = eng._decodeN

        def altered(*args):
            logits, caches = decode(*args)
            return jnp.roll(logits, 1, axis=-1), caches

        eng._decodeN = altered
        return eng

    monkeypatch.setattr(serve_cell, "make_engine", make)
    assert _correct(monkeypatch, capsys) is False


def test_int4_control_is_not_correct(monkeypatch, capsys):
    from repro.launch.train import POLICIES
    monkeypatch.setattr(serve_cell, "serving_policy", lambda: dataclasses.
                        replace(POLICIES["int4"], qweights=True, qcache=True))
    assert _correct(monkeypatch, capsys) is False

