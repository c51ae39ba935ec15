"""Pallas TPU kernel: Shared KV Attention — the paper's GEMM (Fig. 2a).

One grid cell = (shared chunk e, kv head kh, query tile i, kv tile c). The
dispatched query rows for chunk ``e`` and kv head ``kh`` — every
concurrent request that routed here, times the kv head's G query heads,
flattened to (cap*G, D) — are multiplied against the chunk's KV tile
(block_c, D) on the MXU: exactly the memory-bound-GEMV -> compute-bound-
GEMM transformation. Online softmax accumulates across kv tiles in VMEM
scratch; the last kv tile normalizes and writes (out, lse).

Block layout (the TPU's (8, 128) rule: the last two dims of every block
are multiples of (8, 128) or equal the array's own): the store is laid out
per kv head, k/v (E, KH, C, D), so a KV block is (1, 1, block_c, D) with
block_c a multiple of 8 and D the full head dim. Query rows and outputs
are (E, KH, cap*G, D) blocks of (1, 1, block_q, D); the LSE is
(E, KH, cap*G, 1). Dispatch validity (qmask) is applied after the call, so
no lane-sparse mask block enters the kernel.
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
# query rows per grid cell: bounds the (rows, block_c) score tile in VMEM
# when a prefill block dispatches thousands of rows to one chunk
BLOCK_Q = 256


def _kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
            nc: int, scale: float, tot_c: int):
    c = pl.program_id(3)

    @pl.when(c == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0]                              # (block_q, D)
    k = k_ref[0, 0]                              # (block_c, D)
    v = v_ref[0, 0]
    # explicit precision: Mosaic rejects a bf16 matmul at the HIGHEST
    # precision a caller may have set as the default
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            precision=jax.lax.Precision.DEFAULT,
                            preferred_element_type=jnp.float32) * scale
    blk = k.shape[0]
    if tot_c % blk:
        # ragged tail tile (C not a multiple of block_c): the padding past
        # C is unspecified, so mask the scores and zero the values
        pos = c * blk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < tot_c, s, NEG_INF)
        vpos = c * blk + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
        v = jnp.where(vpos < tot_c, v, jnp.zeros_like(v))
    m_prev = m_scr[...]                          # (block_q, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)                       # (block_q, block_c)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(c == nc - 1)
    def _finalize():
        l_safe = jnp.maximum(l_scr[...], 1e-37)
        o_ref[0, 0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = m_scr[...] + jnp.log(l_safe)


@functools.partial(jax.jit, static_argnames=("block_c", "interpret"))
def shared_chunk_attention(qd: jax.Array, k: jax.Array, v: jax.Array,
                           qmask: jax.Array, *, block_c: int = 512,
                           interpret: bool | None = None):
    """qd: (E, cap, H, D); k/v: (E, KH, C, D); qmask: (E, cap) bool.

    Returns (out (E, cap, H, D), lse (E, cap, H) fp32); masked slots carry
    a zero output and a -inf (``NEG_INF``) lse. Grid is
    (E, KH, cap*G / BLOCK_Q, C / block_c); each kv head serves its
    G = H // KH query heads. ``interpret=None`` interprets the kernel body
    on the CPU backend only (``repro.kernels.resolve_interpret``).
    """
    E, cap, H, D = qd.shape
    _, KH, C, _ = k.shape
    G = H // KH
    R = cap * G
    block_c = min(block_c, C)
    block_q = min(BLOCK_Q, R)
    nc = pl.cdiv(C, block_c)
    scale = 1.0 / math.sqrt(D)

    # regroup query rows by kv head: (E, KH, cap*G, D)
    qg = qd.reshape(E, cap, KH, G, D).transpose(0, 2, 1, 3, 4).reshape(
        E, KH, R, D)

    out, lse = pl.pallas_call(
        functools.partial(_kernel, nc=nc, scale=scale, tot_c=C),
        grid=(E, KH, pl.cdiv(R, block_q), nc),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda e, h, i, c: (e, h, i, 0)),
            pl.BlockSpec((1, 1, block_c, D), lambda e, h, i, c: (e, h, c, 0)),
            pl.BlockSpec((1, 1, block_c, D), lambda e, h, i, c: (e, h, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda e, h, i, c: (e, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda e, h, i, c: (e, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((E, KH, R, D), qd.dtype),
            jax.ShapeDtypeStruct((E, KH, R, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=resolve_interpret(interpret),
        name="moska_shared_chunk_attn",
    )(qg, k, v)

    out = out.reshape(E, KH, cap, G, D).transpose(0, 2, 1, 3, 4).reshape(
        E, cap, H, D)
    lse = lse.reshape(E, KH, cap, G).transpose(0, 2, 1, 3).reshape(E, cap, H)
    valid = qmask[:, :, None]
    return (jnp.where(valid[..., None], out, jnp.zeros_like(out)),
            jnp.where(valid, lse, NEG_INF))
