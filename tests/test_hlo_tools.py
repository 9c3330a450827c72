"""HLO cost analyzer: trip-count scaling, dot flops, collective bytes,
unknown-dtype loudness, async -start/-done pair counting."""

import jax
import jax.numpy as jnp
import pytest

from repro.utils.hlo import (UnknownDtypeError, collective_bytes, count_ops,
                             parse_shape_bytes)
from repro.utils.hlo_cost import analyze_hlo
from repro.utils.roofline import Roofline


def _hlo_of(f, *args):
    return jax.jit(f).lower(*args).compile().as_text()


def test_parse_shape_bytes():
    assert parse_shape_bytes("f32[4,8]") == 128
    assert parse_shape_bytes("bf16[2,3]{1,0}") == 12
    assert parse_shape_bytes("(f32[2], u32[4])") == 24
    assert parse_shape_bytes("pred[]") == 1


def test_parse_shape_bytes_unknown_dtype_is_loud():
    with pytest.raises(UnknownDtypeError, match="f8e4m3fn"):
        parse_shape_bytes("f8e4m3fn[16]")
    with pytest.raises(UnknownDtypeError, match="s4"):
        parse_shape_bytes("(f32[2], s4[8])")
    # token is legitimately byte-free, always allowed
    assert parse_shape_bytes("(f32[2], token[])") == 8
    # the escape hatch must be explicit, per dtype
    assert parse_shape_bytes("f8e4m3fn[16]", allow=("f8e4m3fn",)) == 0
    assert parse_shape_bytes("(f32[2], f8e4m3fn[16])",
                             allow=("f8e4m3fn",)) == 8


def test_dot_flops_exact():
    a = jnp.ones((32, 64), jnp.float32)
    b = jnp.ones((64, 16), jnp.float32)
    cost = analyze_hlo(_hlo_of(lambda a, b: a @ b, a, b))
    assert cost.dot_flops == 2 * 32 * 64 * 16


def test_scan_trip_count_multiplies_flops():
    a = jnp.ones((8, 8), jnp.float32)

    def f(a):
        def body(c, _):
            return c @ a, None
        out, _ = jax.lax.scan(body, a, None, length=20)
        return out

    cost = analyze_hlo(_hlo_of(f, a))
    # 20 iterations x (2 * 8^3); XLA may pre/peel one, allow slack
    want = 20 * 2 * 8 ** 3
    assert want * 0.9 <= cost.dot_flops <= want * 1.2, cost.dot_flops
    assert cost.while_count >= 1


def test_nested_scan_trip_counts_compose():
    a = jnp.ones((4, 4), jnp.float32)

    def f(a):
        def outer(c, _):
            def inner(d, _):
                return d @ a, None
            c, _ = jax.lax.scan(inner, c, None, length=5)
            return c, None
        out, _ = jax.lax.scan(outer, a, None, length=3)
        return out

    cost = analyze_hlo(_hlo_of(f, a))
    want = 15 * 2 * 4 ** 3
    assert want * 0.9 <= cost.dot_flops <= want * 1.3


def test_elementwise_flops_counted():
    a = jnp.ones((128,), jnp.float32)
    cost = analyze_hlo(_hlo_of(lambda a: a * 2 + 1, a))
    assert cost.elementwise_flops >= 128  # at least the fused add/mul


def test_collective_bytes_parser_on_synthetic_hlo():
    hlo = """
HloModule m

ENTRY %main (p: f32[16,8]) -> f32[16,8] {
  %p = f32[16,8]{1,0} parameter(0)
  %ag = f32[64,8]{1,0} all-gather(%p), dimensions={0}
  %ar = f32[16,8]{1,0} all-reduce(%p), to_apply=%add
  ROOT %out = f32[16,8]{1,0} copy(%ar)
}
"""
    out = collective_bytes(hlo)
    assert out["by_kind"]["all-gather"]["bytes"] == 64 * 8 * 4
    assert out["by_kind"]["all-reduce"]["bytes"] == 16 * 8 * 4
    assert out["by_kind"]["all-gather"]["count"] == 1


_ASYNC_HLO = """
HloModule m

%fused (a: f64[32]) -> f64[32] {
  %a = f64[32]{0} parameter(0)
  %two = f64[32]{0} multiply(%a, %a)
  ROOT %fr = f64[32]{0} add(%two, %a)
}

ENTRY %main (p: f64[32]) -> f64[32] {
  %p = f64[32]{0} parameter(0)
  %f = f64[32]{0} fusion(%p), kind=kLoop, calls=%fused
  %ar-start = f64[32]{0} all-reduce-start(%f), to_apply=%add
  %ar-done = f64[32]{0} all-reduce-done(%ar-start)
  %ag-start = (f64[32]{0}, f64[128]{0}) all-gather-start(%ar-done), dimensions={0}
  %ag-done = f64[128]{0} all-gather-done(%ag-start)
  %d = f64[32]{0} dot(%p, %p), lhs_contracting_dims={}, rhs_contracting_dims={}
  ROOT %out = f64[32]{0} copy(%ar-done)
}
"""


def test_async_collective_pairs_count_once():
    out = collective_bytes(_ASYNC_HLO)
    # -start/-done describe ONE logical collective each
    assert out["by_kind"]["all-reduce"]["count"] == 1
    assert out["by_kind"]["all-gather"]["count"] == 1
    # all-reduce bytes from the -start result; the tuple-shaped
    # all-gather-start result counts both the operand and output buffers
    assert out["by_kind"]["all-reduce"]["bytes"] == 32 * 8
    assert out["by_kind"]["all-gather"]["bytes"] == (32 + 128) * 8


def test_count_ops_merges_async_pairs_and_sees_fusion_bodies():
    counts = count_ops(_ASYNC_HLO, opnames=("dot", "multiply", "add"))
    assert counts["dot"] == 1
    # ops inside the fusion computation body are instruction lines too
    assert counts["multiply"] == 1
    assert counts["add"] == 1
    # the async pair appears once, under the base opcode -- never as
    # separate -start/-done (or double-counted) entries
    assert counts["all-reduce"] == 1
    assert counts["all-gather"] == 1
    assert not any(k.endswith("-start") or k.endswith("-done")
                   for k in counts)


def test_roofline_terms_and_dominant():
    rl = Roofline(flops=197e12 * 256, bytes_accessed=0.0,
                  collective_bytes=100e9, chips=256,
                  model_flops=100e12 * 256, bytes_min=819e9,
                  hw="tpu-v5e")
    assert abs(rl.compute_s - 1.0) < 1e-9
    assert abs(rl.memory_s - 1.0) < 1e-9
    assert abs(rl.collective_s - 2.0) < 1e-9
    assert rl.dominant == "collective"
    assert 0 < rl.mfu_bound < 1
    assert abs(rl.useful_flops_ratio - 100 / 197) < 1e-9
