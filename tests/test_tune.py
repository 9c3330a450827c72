"""Kernel autotuner: table round-trip, loud invalidation, resolution.

ISSUE 9's unit layer.  Everything here is jax-free (table + planner
resolution are deliberately importable without jax); the measured tuning
path is exercised end-to-end by ``benchmarks/autotune.py`` and the
interpret-mode CI smoke job.
"""

import json

import pytest

from repro.analysis.geometry import audit_tuning_table, validate_tiling
from repro.core.planner import SolverConfig, _resolve_geometry, build_plan
from repro.core.stepspace import DEFAULT_GEOMETRY, Geometry
from repro.tune.search import enumerate_candidates, model_cost
from repro.tune.table import (TABLE_FORMAT_VERSION, TableEntry, TuningTable,
                              density_bucket, kernel_sources_hash)
from repro.utils.roofline import HW_SPECS, detect_hw, get_hw

G_TUNED = Geometry(64, 32, 8)


def _entry(route="dense", n=12, bucket="1.00", dtype="<f8",
           precision="dq_acc", device_kind="any", geometry=G_TUNED):
    return TableEntry(route=route, n=n, density_bucket=bucket, dtype=dtype,
                      precision=precision, device_kind=device_kind,
                      geometry=geometry, predicted_s=2e-3, measured_s=1e-3,
                      default_s=1.5e-3)


# ---------------------------------------------------------------------------
# Geometry + table round-trip
# ---------------------------------------------------------------------------

def test_geometry_tag_roundtrip():
    assert DEFAULT_GEOMETRY.tag() == "128x64x16"
    for g in (DEFAULT_GEOMETRY, G_TUNED, Geometry(8, 8, 8, max_blocks=4)):
        assert Geometry.from_tag(g.tag()) == g


def test_table_roundtrip(tmp_path):
    table = TuningTable()
    table.put(_entry())
    table.put(_entry(route="sparse", bucket="0.25",
                     geometry=Geometry(32, 64, 8)))
    p = str(tmp_path / "t.json")
    table.save(p)
    back = TuningTable.load(p)
    assert back.entries == table.entries
    assert back.kernels_hash == kernel_sources_hash()
    e = back.get("dense", 12, 1.0, "<f8", "dq_acc")
    assert e is not None and e.geometry == G_TUNED
    assert e.speedup == pytest.approx(1.5)
    assert e.mispredict_ratio == pytest.approx(2.0)


def test_table_rejects_version_skew(tmp_path):
    p = str(tmp_path / "t.json")
    table = TuningTable()
    table.put(_entry())
    table.save(p)
    doc = json.load(open(p))
    doc["version"] = TABLE_FORMAT_VERSION + 1
    json.dump(doc, open(p, "w"))
    with pytest.raises(ValueError, match="format version"):
        TuningTable.load(p)


def test_table_rejects_kernel_source_drift(tmp_path):
    # winners measured against other kernel bodies are stale: loud error,
    # with an explicit opt-out for inspection tooling
    p = str(tmp_path / "t.json")
    table = TuningTable()
    table.put(_entry())
    table.save(p)
    doc = json.load(open(p))
    doc["kernels_hash"] = "deadbeefdeadbeef"
    json.dump(doc, open(p, "w"))
    with pytest.raises(ValueError, match="kernel sources changed"):
        TuningTable.load(p)
    assert TuningTable.load(p, strict_hash=False).entries


def test_table_rejects_pl007_violating_entry(tmp_path):
    # a hand-edited table cannot smuggle an invalid geometry past the
    # PR 8 auditor into the planner
    p = str(tmp_path / "t.json")
    table = TuningTable()
    table.put(_entry())
    table.save(p)
    doc = json.load(open(p))
    doc["entries"][0]["geometry"] = "7x5x3"     # nothing power-of-two
    json.dump(doc, open(p, "w"))
    with pytest.raises(ValueError, match="PL007"):
        TuningTable.load(p)
    # the lint-side audit reports the same file instead of raising
    assert audit_tuning_table(p)
    assert audit_tuning_table(str(tmp_path / "missing.json")) == []


def test_density_bucketing():
    assert density_bucket(0.05) == "0.25"
    assert density_bucket(0.25) == "0.25"
    assert density_bucket(0.26) == "0.50"
    assert density_bucket(0.80) == "1.00"
    assert density_bucket(1.00) == "1.00"


def test_table_device_kind_wildcard():
    table = TuningTable()
    table.put(_entry(device_kind="any"))
    # a concrete host kind falls back to the "any" wildcard row
    assert table.resolve("dense", 12, 1.0, "<f8", "dq_acc",
                         device_kind="tpu v5e") == G_TUNED
    assert table.resolve("dense", 13, 1.0, "<f8", "dq_acc") is None


# ---------------------------------------------------------------------------
# candidate enumeration + cost model
# ---------------------------------------------------------------------------

def test_enumerate_candidates_valid_and_deduped():
    for n in (8, 12, 16):
        cands = enumerate_candidates(n)
        assert cands[0] == DEFAULT_GEOMETRY
        resolved = set()
        for g in cands:
            assert validate_tiling(n, g.lanes, g.steps_per_chunk,
                                   g.window) == []
            resolved.add(g.kernel_geometry(n))
        assert len(resolved) == len(cands), "clamped duplicates survived"


def test_model_cost_orders_sanely():
    # monotone in n and batch; complex costs more than real; the model
    # only needs to RANK candidates, so only ordering is asserted
    g = DEFAULT_GEOMETRY
    assert model_cost(g, 16) > model_cost(g, 12)
    assert model_cost(g, 12, batch=64) > model_cost(g, 12, batch=1)
    assert model_cost(g, 12, route="complex") > model_cost(g, 12)
    assert model_cost(g, 12, route="sparse", density=0.2) \
        < model_cost(g, 12, route="sparse", density=1.0)


# ---------------------------------------------------------------------------
# planner resolution: config override > table hit > defaults
# ---------------------------------------------------------------------------

def test_resolve_precedence(tmp_path):
    p = str(tmp_path / "t.json")
    table = TuningTable()
    table.put(_entry())
    table.save(p)
    over = Geometry(8, 8, 8)
    # explicit config override wins even over a table hit
    assert _resolve_geometry(
        SolverConfig(geometry=over, tuning_table=p),
        "dense", 12, 1.0, "<f8", "dq_acc") == over
    # table hit
    assert _resolve_geometry(
        SolverConfig(tuning_table=p),
        "dense", 12, 1.0, "<f8", "dq_acc") == G_TUNED
    # no table, no override: kernel defaults (None)
    assert _resolve_geometry(
        SolverConfig(), "dense", 12, 1.0, "<f8", "dq_acc") is None
    # campaign wave bodies fall back to the dense entry
    assert _resolve_geometry(
        SolverConfig(tuning_table=p),
        "step_sharded", 12, 1.0, "<f8", "dq_acc") == G_TUNED


def test_resolve_missing_table_is_loud(tmp_path):
    cfg = SolverConfig(tuning_table=str(tmp_path / "nope.json"))
    with pytest.raises(OSError):
        _resolve_geometry(cfg, "dense", 12, 1.0, "<f8", "dq_acc")


# ---------------------------------------------------------------------------
# geometry is part of plan identity
# ---------------------------------------------------------------------------

def test_plan_records_geometry_in_identity(tmp_path):
    import numpy as np
    A = np.random.default_rng(0).uniform(0.2, 1.0, (8, 8))
    base = dict(backend="pallas", preprocess=False)
    plain = build_plan([A], SolverConfig(**base), batched=True)
    tuned = build_plan([A], SolverConfig(geometry=G_TUNED, **base),
                       batched=True)
    assert plain.leaves[0].geometry is None
    assert tuned.leaves[0].geometry == G_TUNED
    # fingerprint and --plan-json both carry the resolved geometry
    assert plain.fingerprint() != tuned.fingerprint()
    leaf_json = tuned.to_json()["leaves"][0]
    assert leaf_json["geometry"] == G_TUNED.tag()
    assert plain.to_json()["leaves"][0]["geometry"] is None
    # two distinct geometries are two distinct identities
    tuned2 = build_plan([A], SolverConfig(geometry=Geometry(8, 8, 8),
                                          **base), batched=True)
    assert tuned2.fingerprint() != tuned.fingerprint()
    # non-pallas backends never carry geometry, even when configured
    jnp_plan = build_plan([A], SolverConfig(geometry=G_TUNED,
                                            preprocess=False), batched=True)
    assert jnp_plan.leaves[0].geometry is None


# ---------------------------------------------------------------------------
# hardware registry
# ---------------------------------------------------------------------------

def test_detect_hw_precedence(monkeypatch):
    monkeypatch.delenv("REPRO_HW", raising=False)
    assert detect_hw("TPU v5 lite").name == "tpu-v5e"
    assert detect_hw("TPU v4").name == "tpu-v4"
    # explicit argument beats the environment override ...
    monkeypatch.setenv("REPRO_HW", "tpu-v5p")
    assert detect_hw("TPU v4").name == "tpu-v4"
    # ... and the environment override beats autodetection
    assert detect_hw().name == "tpu-v5p"
    assert get_hw("tpu-v5e") == HW_SPECS["tpu-v5e"]


@pytest.mark.parametrize("resolve", [
    lambda: detect_hw("weird accelerator"),
    lambda: get_hw("no-such-hw"),
], ids=["detect_hw", "get_hw"])
def test_unknown_hardware_raises(monkeypatch, resolve):
    monkeypatch.delenv("REPRO_HW", raising=False)
    with pytest.raises((KeyError, ValueError), match="no hardware spec"):
        resolve()
