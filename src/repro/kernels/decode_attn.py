"""Pallas TPU kernel: unique-KV decode attention (flash-decoding GEMV).

This is the paper's memory-bound path (Fig. 2a left): one query per request
against its private KV cache. The kernel tiles the cache sequence into
(block_s, D) VMEM blocks — grid (batch, kv_head, seq tile) — with online-
softmax accumulation in scratch and ragged masking from per-request
``kv_len``. It exists to keep the Unique-KV node honest/fast; the roofline
contrast between this kernel (intensity ~G) and `shared_chunk_attn`
(intensity ~cap·G) is the paper's core claim, measured in
benchmarks/bench_kernels.py.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
            m_scr, l_scr, acc_scr, *, ns: int, block_s: int, scale: float):
    s_idx = pl.program_id(2)

    q = q_ref[0, 0].astype(jnp.float32)          # (G, D)
    k = k_ref[0, :, 0].astype(jnp.float32)       # (block_s, D)
    v = v_ref[0, :, 0].astype(jnp.float32)

    @pl.when(s_idx == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    kv_len = len_ref[0]
    pos = s_idx * block_s + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = pos < kv_len
    s = jnp.where(valid, s, NEG_INF)
    # zero V on invalid rows: OOB tile padding must not produce 0*NaN
    vpos = s_idx * block_s + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
    v = jnp.where(vpos < kv_len, v, 0.0)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(s_idx == ns - 1)
    def _finalize():
        l_safe = jnp.maximum(l_scr[...], 1e-37)
        o_ref[0, 0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_scr[...] + jnp.log(l_safe))[:, 0]


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     kv_len: jax.Array, *, block_s: int = 1024,
                     interpret: bool | None = None):
    """q: (B, H, D); k/v: (B, S, KH, D); kv_len: (B,) valid lengths.

    Returns (out (B, H, D), lse (B, H) fp32).
    """
    B, H, D = q.shape
    _, S, KH, _ = k.shape
    G = H // KH
    block_s = min(block_s, S)
    ns = pl.cdiv(S, block_s)
    scale = 1.0 / math.sqrt(D)

    qg = q.reshape(B, KH, G, D)
    lens = kv_len.astype(jnp.int32)

    out, lse = pl.pallas_call(
        functools.partial(_kernel, ns=ns, block_s=block_s, scale=scale),
        grid=(B, KH, ns),
        in_specs=[
            pl.BlockSpec((1,), lambda b, h, s: (b,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, G, D), lambda b, h, s: (b, h, 0, 0)),
            pl.BlockSpec((1, block_s, 1, D), lambda b, h, s: (b, s, h, 0)),
            pl.BlockSpec((1, block_s, 1, D), lambda b, h, s: (b, s, h, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, s: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, G), lambda b, h, s: (b, h, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, KH, G, D), q.dtype),
            jax.ShapeDtypeStruct((B, KH, G), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
        name="moska_unique_decode_attn",
    )(lens, qg, k, v)

    return out.reshape(B, H, D), lse.reshape(B, H)
