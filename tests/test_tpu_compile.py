"""Compile the served Pallas kernels for a TPU v5e that is described, not
attached: the chip's compiler refuses block layouts that interpret mode
accepts (the last two dims of every block must be multiples of (8, 128) or
equal the array's own), so these tests guard the kernels' layouts on a
CPU-only host. The decode step's KV append is checked the same way for the
loop the chip's compiler makes of some scatters, and the whole decode step
for cache-sized copies.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and every test worker imports this file. The persistent compilation cache
is off around these compiles, because an entry written for a described chip
cannot be read back without one.
"""
import dataclasses
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import decode_attn
from repro.kernels.lse_merge import lse_merge
from repro.kvcache.cache import abstract_kv_cache, append_token
from repro.kernels.router_score import router_scores
from repro.kernels.shared_chunk_attn import shared_chunk_attention


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler can be loaded here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("name,E,cap,H,KH,D", [
    ("tinyllama-1.1b", 8, 16, 32, 4, 64),
    ("llama3-8b", 8, 16, 32, 8, 128),
    ("tinyllama-1.1b-prefill", 8, 8 * 128, 32, 4, 64),   # 8 x 128-token block
])
def test_shared_chunk_attention_compiles(one_chip, name, E, cap, H, KH, D):
    C = 2048
    kv = ((E, KH, C, D), jnp.bfloat16)
    compiled = _compile(
        functools.partial(shared_chunk_attention, interpret=False), one_chip,
        ((E, cap, H, D), jnp.bfloat16), kv, kv, ((E, cap), jnp.bool_))
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= 2 * E * KH * C * D * 2


def test_router_scores_compiles(one_chip):
    compiled = _compile(
        functools.partial(router_scores, interpret=False), one_chip,
        ((128, 32, 64), jnp.float32), ((512, 4, 64), jnp.bfloat16))
    assert "tpu_custom_call" in compiled.as_text()


def test_lse_merge_compiles(one_chip):
    compiled = _compile(
        functools.partial(lse_merge, interpret=False), one_chip,
        ((2, 256, 32, 64), jnp.bfloat16), ((2, 256, 32), jnp.float32))
    assert "tpu_custom_call" in compiled.as_text()


def test_slot_append_compiles_to_a_scatter_without_a_loop(one_chip):
    kv = ((32, 256, 4, 64), jnp.bfloat16)
    new = ((32, 4, 64), jnp.bfloat16)
    compiled = _compile(append_token, one_chip, kv, kv, new, new,
                        ((32,), jnp.int32))
    text = compiled.as_text()
    assert "scatter(" in text
    assert " while(" not in text


_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.-]+) = \w+\[([\d,]*)\]\S* (\S+)\(")


def _large_layout_ops(hlo_text: str, min_elems: int):
    """Copies, dynamic slices and dynamic-update-slices (or fusions named
    for them) whose result holds at least ``min_elems`` elements."""
    found = []
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, dims, op = m.groups()
        if not any(w in name or w == op for w in
                   ("copy", "dynamic-slice", "dynamic-update-slice")):
            continue
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        if n >= min_elems:
            found.append(line.strip()[:160])
    return found


def test_decode_step_reads_and_writes_the_cache_in_place(one_chip,
                                                         monkeypatch):
    """qwen1.5-0.5b's decode step at its cell's shapes (32 slots, max_seq
    768), two layers: the stacked lane-dense cache is loop state written
    one row per request and read in place by the attention kernel, so no
    layer slab is copied, sliced out or written back, the temporaries stay
    under one layer's K and V, and the donated cache aliases its unpadded
    bytes."""
    from repro.configs import get_config
    from repro.models import dense
    # the kernel picks interpret mode from the default backend, the CPU
    # here; compile it with Mosaic for the described chip
    monkeypatch.setattr(decode_attn, "resolve_interpret",
                        lambda interpret: bool(interpret))
    jax.clear_caches()
    cfg = dataclasses.replace(get_config("qwen1.5-0.5b"), num_layers=2)
    B, S, KH, D = 32, 768, cfg.num_kv_heads, cfg.head_dim

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: dense.init_params(cfg, jax.random.PRNGKey(0))))
    cache = on_chip(abstract_kv_cache(cfg.num_layers, B, S, KH, D))
    tokens = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(lambda p, t, c: dense.decode_step(cfg, p, t, c),
                       donate_argnums=(2,)).lower(params, tokens,
                                                  cache).compile()
    jax.clear_caches()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    slab = B * S * KH * D
    assert _large_layout_ops(text, slab) == []
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2 * slab * 2
    kv_bytes = 2 * cfg.num_layers * slab * 2
    # plus the (B,) lengths and offsets, each padded to one 512-byte tile
    assert mem.alias_size_in_bytes == kv_bytes + 2 * 512
