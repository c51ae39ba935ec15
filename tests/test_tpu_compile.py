"""Compile the served Pallas kernels for a TPU v5e that is described, not
attached: the chip's compiler refuses block layouts that interpret mode
accepts (the last two dims of every block must be multiples of (8, 128) or
equal the array's own), so these tests guard the kernels' layouts on a
CPU-only host. The decode step's KV append is checked the same way for the
loop the chip's compiler makes of some scatters.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and every test worker imports this file. The persistent compilation cache
is off around these compiles, because an entry written for a described chip
cannot be read back without one.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.lse_merge import lse_merge
from repro.kvcache.cache import append_token
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
