"""The campaign cell's own code path at a tiny size on the CPU, on four
virtual devices, in one subprocess (``rehearse_campaign.py``): correct
when sound, every new per-layer metric read, and not correct under each
planted fault."""

import json
import os
import subprocess
import sys

import pytest

import harness

CELL = "dense_n31.campaign4"
SPEC = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
FAULTS = ["control", "slice_left_out", "stale_answer"]


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    proc = subprocess.run(
        [sys.executable,
         os.path.join(harness.BENCH, "tests", "rehearse_campaign.py"),
         "sound", "traced", *FAULTS],
        capture_output=True, text=True, timeout=600, cwd=harness.ROOT,
        env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.splitlines()[-1])


def _names(group):
    return sorted(m["name"] for m in SPEC[group]
                  if "workloads" not in m or CELL in m["workloads"])


def test_cell_spec():
    cell = harness.load_cell(CELL)
    assert cell.chips == 4 and cell.traffic["loop"] == "campaign"
    assert _names("end_to_end") == ["perms_per_s", "setup_s"]
    # the planner's own threshold routes the cell's n to the campaign
    assert "campaign_threshold" not in cell.config["solver"]


def test_sound_run_is_correct_on_four_devices(runs):
    assert runs["devices"] == 4
    out, c = runs["sound"]["result"], runs["sound"]["counters"]
    assert out["correct"] is True and out["attempted"] > 0
    assert out["checks"]["unanswered"]["value"] == 0
    assert sorted(out["metrics"]) == _names("end_to_end")
    assert out["device"]["count"] == 4
    # 8 slices in waves of 4, a checkpoint after each
    assert c["campaign_waves"] == 2 * c["jobs_run"]
    assert c["campaign_slices"] == 8 * c["jobs_run"]
    assert c["campaign_retried_waves"] == 0
    assert c["campaign_checkpoint_bytes"] > 0
    assert c["compiles_in_window"] == 0


def test_traced_run_reads_every_span_metric(runs):
    out = runs["traced"]["result"]
    assert out["correct"] is True
    per_layer = [m for m in SPEC["per_layer"]
                 if CELL in m.get("workloads", ())]
    # the CPU has no device plane: device-trace metrics stay silent
    want = sorted(m["name"] for m in per_layer
                  if m["source"] != "device_trace")
    assert want == ["campaign.checkpoint_ms", "campaign.wave_host_ms",
                    "device_wait_ms.campaign4"]
    assert sorted(out["metrics"]) == want
    assert all(m["value"] > 0 for m in out["metrics"].values())


@pytest.mark.parametrize("fault", FAULTS)
def test_planted_fault_is_not_correct(runs, fault):
    out = runs[fault]["result"]
    assert out["correct"] is False
    c = out["checks"]["max_rel_err"]
    assert c["value"] > c["limit"]
    assert out["checks"]["unanswered"]["value"] == 0
