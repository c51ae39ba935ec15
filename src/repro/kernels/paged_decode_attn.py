"""Pallas TPU kernel: paged unique-KV decode attention.

Same flash-decoding GEMV as ``kernels/decode_attn.py``, but K/V live in a
shared block pool ``(N, block_size, KH, D)`` instead of per-request
``max_seq`` slabs; each request's pages are named by a block table
``(B, M)``. The table and the ragged lengths ride in as **scalar-prefetch
operands** (``pltpu.PrefetchScalarGridSpec``), so the K/V BlockSpec
index_map dereferences ``table[b, m]`` to pick the physical pool page for
grid step ``(b, h, m)`` — the kernel itself never materialises a gathered
contiguous cache, which is the point: HBM traffic is one page per grid
step regardless of how fragmented the mapping is.

``paged_decode_attention_ref`` is the jnp oracle: gather the pool through
the table into a contiguous ``(B, M * bs, KH, D)`` view and run the dense
``kernels.ref.decode_attention_ref``. Null-page garbage past ``kv_len``
is masked to exact-zero probability, so the oracle is *bitwise* equal to
the dense reference on an equivalently-filled slotted cache — the
engine's paged/slotted bit-identity rests on this (see
tests/test_kernels.py).
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels import ref
from repro.kernels import resolve_interpret
from repro.kvcache.paged import gather_layer

NEG_INF = -1e30


def paged_decode_attention_ref(q: jax.Array, k_pool: jax.Array,
                               v_pool: jax.Array, table: jax.Array,
                               kv_len: jax.Array
                               ) -> Tuple[jax.Array, jax.Array]:
    """jnp oracle: table gather + dense decode reference.

    q: (B, H, D); k_pool/v_pool: (N, bs, KH, D); table: (B, M) int32;
    kv_len: (B,). Returns (out (B, H, D), lse (B, H) fp32).
    """
    k = gather_layer(k_pool, table)
    v = gather_layer(v_pool, table)
    return ref.decode_attention_ref(q, k, v, kv_len)


def _kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
            m_scr, l_scr, acc_scr, *, nm: int, bs: int, scale: float):
    b_idx = pl.program_id(0)
    m_idx = pl.program_id(2)

    q = q_ref[0, 0].astype(jnp.float32)          # (G, D)
    k = k_ref[0, :, 0].astype(jnp.float32)       # (bs, D) — one pool page
    v = v_ref[0, :, 0].astype(jnp.float32)

    @pl.when(m_idx == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    kv_len = len_ref[b_idx]
    # logical positions of this page: the m-th table entry covers
    # [m*bs, (m+1)*bs) regardless of which physical page backs it
    pos = m_idx * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < kv_len, s, NEG_INF)
    # zero V on masked rows: null-page garbage must not produce 0*NaN
    vpos = m_idx * bs + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
    v = jnp.where(vpos < kv_len, v, 0.0)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(m_idx == nm - 1)
    def _finalize():
        l_safe = jnp.maximum(l_scr[...], 1e-37)
        o_ref[0, 0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_scr[...] + jnp.log(l_safe))[:, 0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, table: jax.Array,
                           kv_len: jax.Array, *,
                           interpret: bool | None = None
                           ) -> Tuple[jax.Array, jax.Array]:
    """q: (B, H, D); k_pool/v_pool: (N, bs, KH, D) physical page pools;
    table: (B, M) int32 block tables (NULL-padded); kv_len: (B,).

    Grid (B, KH, M): the m-th sequence tile of request b reads pool page
    ``table[b, m]`` directly via the scalar-prefetched index_map.
    Returns (out (B, H, D), lse (B, H) fp32).
    """
    B, H, D = q.shape
    N, bs, KH, _ = k_pool.shape
    M = table.shape[1]
    G = H // KH
    scale = 1.0 / math.sqrt(D)

    qg = q.reshape(B, KH, G, D)
    tbl = table.astype(jnp.int32)
    lens = kv_len.astype(jnp.int32)

    def kv_spec():
        # page index comes from the prefetched table, not the grid
        return pl.BlockSpec((1, bs, 1, D),
                            lambda b, h, m, tbl, lens: (tbl[b, m], 0, h, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KH, M),
        in_specs=[
            pl.BlockSpec((1, 1, G, D),
                         lambda b, h, m, tbl, lens: (b, h, 0, 0)),
            kv_spec(),
            kv_spec(),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, G, D),
                         lambda b, h, m, tbl, lens: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, G),
                         lambda b, h, m, tbl, lens: (b, h, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )

    out, lse = pl.pallas_call(
        functools.partial(_kernel, nm=M, bs=bs, scale=scale),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, KH, G, D), q.dtype),
            jax.ShapeDtypeStruct((B, KH, G), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
        name="moska_paged_decode_attn",
    )(tbl, lens, qg, k_pool, v_pool)

    return out.reshape(B, H, D), lse.reshape(B, H)
