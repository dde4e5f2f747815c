"""``bench/run.py`` end to end on the CPU at a tiny size, with its look
for a chip bypassed here and nowhere else."""

import json

import pytest

from bench import run, spec, trace
from bench.tests import smoke

KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def _last_line(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


def _names(metrics, cell):
    return sorted(m["name"] for m in metrics
                  if cell in m.get("workloads", [cell]))


CELL = "qwen2-0.5b.serve.chat-sat"


def test_traced_result_line(monkeypatch, capsys):
    cell = CELL
    smoke.patch_run(monkeypatch, run, trace)
    assert run.main(["--workload", cell, "--seed", str(2**33 + 7),
                     "--seconds", "1", "--trace", "1"]) == 0
    line = _last_line(capsys)
    bench = spec.load_benchmark()
    assert list(line) == KEYS + ["breakdown", "checks"]
    assert sorted(line["metrics"]) == _names(bench["per_layer"], cell)
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["checks"]) == set(smoke.small_cell(cell).limits)
    assert all(set(v) == {"value", "limit"} for v in line["checks"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= \
        set(line["device"])


def test_no_chip_no_result(monkeypatch, capsys):
    """Here JAX sees only the CPU: the run exits non-zero and prints no
    result, so a CPU number never appears under a device metric."""
    monkeypatch.setattr(run, "_setup_env", lambda: None)
    monkeypatch.setattr(run, "_enable_cache", lambda: "off")
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", CELL, "--seed", "1",
                  "--seconds", "1", "--trace", "0"])
    assert e.value.code != 0
    assert capsys.readouterr().out == ""


class _Child:
    """A stand-in child process that exits with the next given code."""

    def __init__(self, codes, seen, cmd, env):
        seen.append((cmd, dict(env)))
        self.returncode = codes.pop(0)

    def wait(self):
        return self.returncode

    def poll(self):
        return self.returncode


def _launch(monkeypatch, codes):
    seen = []
    monkeypatch.setattr(run.subprocess, "Popen",
                        lambda cmd, env: _Child(codes, seen, cmd, env))
    return run.launch(["--workload", CELL, "--seed", "3"]), seen


def test_launcher_reruns_a_child_that_compiled(monkeypatch):
    """A child whose set-up compiled is followed by one fresh child that
    loads from the cache; both count set-up from the command's start."""
    rc, seen = _launch(monkeypatch, [run.RECOMPILED, 0])
    assert rc == 0
    assert [env[run.LAUNCH_TRY] for _, env in seen] == ["1", "2"]
    assert {env[run.LAUNCH_T0] for _, env in seen} == {repr(run.T0)}
    assert seen[0][0] == seen[1][0] and seen[0][0][-4:] == [
        "--workload", CELL, "--seed", "3"]


@pytest.mark.parametrize("codes,rc", [([0], 0), ([1], 1), ([-15], 143),
                                      ([run.RECOMPILED, run.RECOMPILED], 1)])
def test_launcher_passes_on_the_childs_exit(monkeypatch, codes, rc):
    """One child where it compiled nothing; a child's failure or signal
    is the command's; a second compiling child ends the run."""
    got, seen = _launch(monkeypatch, list(codes))
    assert got == rc and len(seen) == len(codes)


def test_command_without_chip_exits_nonzero():
    """The command as the benchmark is run, where JAX sees only the CPU:
    a non-zero exit and no result."""
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop(run.LAUNCH_TRY, None)
    p = subprocess.run([sys.executable, str(spec.ROOT / "bench" / "run.py"),
                        "--workload", CELL, "--seed", "1", "--seconds", "1",
                        "--trace", "0"], env=env, capture_output=True,
                       text=True, timeout=300, cwd=spec.ROOT)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr
