"""While-aware HLO coster: trip-count multiplication, dot flops, collective
byte extraction — validated on real compiled modules."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_cost import HloModule, analyze_hlo, _shape_bytes
from repro.launch.roofline import V5E, collective_bytes, peaks


def _compile(f, *args):
    return jax.jit(f).lower(*args).compile()


def test_scan_trip_count_multiplied():
    def f(x, ws):
        def body(c, w):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, ws)
        return y

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    flops = {}
    for L in (2, 16):
        ws = jax.ShapeDtypeStruct((L, 128, 128), jnp.float32)
        cost = analyze_hlo(_compile(f, x, ws).as_text())
        flops[L] = cost.flops
        assert cost.flops == pytest.approx(2 * 128**3 * L, rel=0.01), L
    assert flops[16] == pytest.approx(8 * flops[2], rel=0.01)


def test_nested_scan_trip_counts():
    def f(x, ws):
        def outer(c, w):
            def inner(c2, _):
                return jnp.tanh(c2 @ w), None
            c2, _ = jax.lax.scan(inner, c, None, length=3)
            return c2, None
        y, _ = jax.lax.scan(outer, x, ws)
        return y

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((4, 64, 64), jnp.float32)
    cost = analyze_hlo(_compile(f, x, ws).as_text())
    assert cost.flops == pytest.approx(2 * 64**3 * 4 * 3, rel=0.01)


def test_dot_flops_rectangular():
    def f(a, b):
        return a @ b
    a = jax.ShapeDtypeStruct((64, 256), jnp.float32)
    b = jax.ShapeDtypeStruct((256, 32), jnp.float32)
    cost = analyze_hlo(_compile(f, a, b).as_text())
    assert cost.flops == pytest.approx(2 * 64 * 256 * 32, rel=0.01)


def test_shape_bytes_parses_tuples_and_dtypes():
    assert _shape_bytes("f32[4,8]") == 128
    assert _shape_bytes("bf16[10]") == 20
    assert _shape_bytes("(s32[], f32[256,256]{1,0})") == 4 + 256 * 256 * 4
    assert _shape_bytes("pred[]") == 1


def test_collective_bytes_regex():
    hlo = """
  %ag = f32[64,128]{1,0} all-gather(%x), dimensions={0}
  %ar.1 = bf16[32]{0} all-reduce(%y), to_apply=%sum
  %done = f32[8]{0} all-gather-done(%start)
"""
    out = collective_bytes(hlo)
    assert out["all-gather"] == 64 * 128 * 4
    assert out["all-reduce"] == 32 * 2


def test_module_entry_detection():
    def f(x):
        return x * 2 + 1
    x = jax.ShapeDtypeStruct((32,), jnp.float32)
    m = HloModule(_compile(f, x).as_text())
    assert m.entry is not None
    assert m.entry in m.computations


def test_peaks_keyed_by_device_kind():
    """The v5e's published peaks are found by its device_kind; any other
    kind raises instead of falling back to a default chip."""
    v5e = peaks(V5E)
    assert v5e["peak_flops_bf16"] == 197e12 and v5e["hbm_bw"] == 819e9
    for kind in ("cpu", "TPU v4", "TPU v6 lite"):
        with pytest.raises(KeyError):
            peaks(kind)
