"""Each cell's own code path at a tiny size on the CPU, and the command's
refusal to run anywhere but on a TPU."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import harness
import rehearse
import run as bench_run
from peaks import UnknownDevice

SPEC = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in SPEC["workloads"]]
IN_PROCESS = [c for c in CELLS if harness.load_cell(c).chips == 1]


def _names(metrics, cell):
    return sorted(m["name"] for m in metrics
                  if "workloads" not in m or cell in m["workloads"])


def _check_schema(out: dict, cell: str, trace: bool):
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] > 0 and out["failed"] == 0
    for k in ("platform", "kind", "count", "memory_peak_bytes"):
        assert k in out["device"]
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"}
    for c in out["checks"].values():
        assert set(c) == {"value", "limit"}
    if trace:
        assert {"busy_s", "window_s"} <= set(out["device"])
        assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert sorted(out["metrics"]) == _names(SPEC["end_to_end"], cell)


def _subprocess_env() -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    return env


@pytest.mark.parametrize("cell", IN_PROCESS)
def test_cell_rehearsal_end_to_end(cell):
    out = rehearse.run_tiny(cell)
    _check_schema(out, cell, trace=False)
    assert out["correct"] is True
    assert out["checks"]["unanswered"]["value"] == 0


@pytest.mark.parametrize("cell", IN_PROCESS)
def test_cell_rehearsal_traced(cell):
    out = rehearse.run_tiny(cell, trace=True)
    _check_schema(out, cell, trace=True)
    assert out["correct"] is True
    # the CPU has no device plane: device-trace metrics stay silent
    per_layer = {m["name"]: m for m in SPEC["per_layer"]}
    for name in out["metrics"]:
        assert per_layer[name]["source"] != "device_trace"


@pytest.mark.parametrize("cell", IN_PROCESS)
def test_altered_answer_is_not_correct(cell, monkeypatch):
    from repro.core.solver import PermanentSolver
    monkeypatch.setattr(PermanentSolver, "execute",
                        rehearse.faulty_execute("altered_answer"))
    out = rehearse.run_tiny(cell)
    assert out["correct"] is False
    c = out["checks"]["max_rel_err"]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("cell", IN_PROCESS)
@pytest.mark.parametrize("fault", ["half_batch_left_out", "stale_answer"])
def test_planted_fault_is_not_correct(cell, fault, monkeypatch):
    """A fault planted under the harness, every other part of the run as
    it stands, makes ``correct`` false by the compared number."""
    from repro.core.solver import PermanentSolver
    monkeypatch.setattr(PermanentSolver, "execute",
                        rehearse.faulty_execute(fault))
    out = rehearse.run_tiny(cell)
    assert out["correct"] is False
    c = out["checks"]["max_rel_err"]
    assert c["value"] > c["limit"]


def test_command_refuses_without_tpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(harness.BENCH, "run.py"),
         "--workload", CELLS[0], "--seed", str(2 ** 31 + 5),
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120, cwd=harness.ROOT,
        env=_subprocess_env())
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "TPU" in proc.stderr


def test_command_refuses_in_bare_checkout(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0],
         "--seed", "7", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120, cwd=tmp_path,
        env=_subprocess_env())
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout


class _Dev:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


def test_require_tpu_refuses_cpu_few_chips_and_unknown_kind():
    with pytest.raises(bench_run.NoChip):
        bench_run.require_tpu(1, [_Dev("cpu", "cpu")])
    with pytest.raises(bench_run.NoChip):
        bench_run.require_tpu(4, [_Dev("tpu", "TPU v5 lite")])
    with pytest.raises(UnknownDevice):
        bench_run.require_tpu(1, [_Dev("tpu", "TPU v99")])
    devs = [_Dev("tpu", "TPU v5 lite")] * 4
    assert bench_run.require_tpu(4, devs) is devs
