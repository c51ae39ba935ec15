"""Pallas TPU kernel: unique-KV decode attention (flash-decoding GEMV).

This is the paper's memory-bound path (Fig. 2a left): one query per request
against its private KV cache. It reads the layer-stacked, lane-dense cache
``(L, B, S, KH·D)`` where it lies: the layer index and the per-request
``kv_len`` are scalar-prefetched into SMEM and pick each ``(1, 1, block_s,
KH·D)`` block in ``index_map``, so no layer slice, head split or relayout of
the cache is ever made. Grid (batch, seq tile) with online-softmax
accumulation in scratch; tiles past ``kv_len`` (or before the window) map
to the last valid tile, which the pipeline does not fetch again, and are
not computed.

All heads of a request share one MXU pass: the query is laid out
block-diagonally, ``(H, KH·D)`` with row ``h·G + g`` holding query head
``(h, g)`` in the lanes of kv head ``h`` and zeros elsewhere, so
``q_bd @ K^T`` is each head's scores and ``p @ V`` holds each head's output
in its own kv head's lanes, which the wrapper picks out.
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


def _tile_range(kv_len, window: int, block_s: int, ns: int):
    """First and last seq tile holding a position the query attends to."""
    last = jnp.clip((kv_len - 1) // block_s, 0, ns - 1)
    if not window:
        return 0, last
    first = jnp.clip((kv_len - window) // block_s, 0, last)
    return first, last


def _kernel(layer_ref, len_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
            m_scr, l_scr, acc_scr, *, ns: int, block_s: int, window: int,
            scale: float):
    del layer_ref
    b, s_idx = pl.program_id(0), pl.program_id(1)
    kv_len = len_ref[b]
    first, last = _tile_range(kv_len, window, block_s, ns)

    @pl.when(s_idx == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when((s_idx >= first) & (s_idx <= last))
    def _step():
        q = q_ref[0]                                   # (H, KH·D)
        k = k_ref[0, 0]                                # (block_s, KH·D)
        v = v_ref[0, 0]
        ct = jnp.promote_types(q.dtype, k.dtype)
        # explicit precision: Mosaic rejects a bf16 matmul at the HIGHEST
        # precision a caller may have set as the default
        s = jax.lax.dot_general(q.astype(ct), k.astype(ct),
                                (((1,), (1,)), ((), ())),
                                precision=jax.lax.Precision.DEFAULT,
                                preferred_element_type=jnp.float32) * scale
        pos = s_idx * block_s + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                                         1)
        valid = pos < kv_len
        if window:
            valid &= pos >= kv_len - window
        s = jnp.where(valid, s, NEG_INF)
        # zero V past kv_len: a tile's out-of-bounds padding must not
        # make 0 * NaN
        vpos = s_idx * block_s + jax.lax.broadcasted_iota(jnp.int32,
                                                          v.shape, 0)
        v = jnp.where(vpos < kv_len, v, jnp.zeros((), v.dtype))

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(s_idx == ns - 1)
    def _finalize():
        l_safe = jnp.maximum(l_scr[...], 1e-37)
        o_ref[0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = m_scr[...] + jnp.log(l_safe)


@functools.partial(jax.jit,
                   static_argnames=("window", "block_s", "interpret"))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     kv_len: jax.Array, layer, *, window: int = 0,
                     block_s: int = 256, interpret: bool | None = None):
    """One query per request against layer ``layer`` of a stacked cache.

    q: (B, H, D); k/v: (L, B, S, KH·D), head ``h`` in lanes
    ``[h·D, (h+1)·D)``; kv_len: (B,) valid lengths; layer: int32 scalar.
    ``window`` > 0 attends only to the last ``window`` positions, as
    ``layers.decode_attention`` does. Returns (out (B, H, D) in q's dtype,
    lse (B, H) fp32).
    """
    B, H, D = q.shape
    _, _, S, W = k.shape
    KH = W // D
    G = H // KH
    block_s = min(block_s, S)
    ns = pl.cdiv(S, block_s)

    # block-diagonal query: (B, H, KH·D), zeros outside the head's kv lanes
    own = jnp.eye(KH, dtype=q.dtype)[None, :, None, :, None]
    q_bd = (q.reshape(B, KH, G, 1, D) * own).reshape(B, H, W)
    lens = kv_len.astype(jnp.int32)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def kv_map(b, s, layer_ref, len_ref):
        first, last = _tile_range(len_ref[b], window, block_s, ns)
        return layer_ref[0], b, jnp.clip(s, first, last), 0

    kv_spec = pl.BlockSpec((1, 1, block_s, W), kv_map)
    out, lse = pl.pallas_call(
        functools.partial(_kernel, ns=ns, block_s=block_s, window=window,
                          scale=1.0 / math.sqrt(D)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, ns),
            in_specs=[pl.BlockSpec((1, H, W), lambda b, s, *_: (b, 0, 0)),
                      kv_spec, kv_spec],
            out_specs=[pl.BlockSpec((1, H, W), lambda b, s, *_: (b, 0, 0)),
                       pl.BlockSpec((1, H, 1), lambda b, s, *_: (b, 0, 0))],
            scratch_shapes=[pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, W), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((B, H, W), q.dtype),
                   jax.ShapeDtypeStruct((B, H, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
        name="moska_unique_decode_attn",
    )(layer, lens, q_bd, k, v)

    # row h·G + g keeps the lanes of its own kv head h
    out = jnp.diagonal(out.reshape(B, KH, G, KH, D), axis1=1, axis2=3)
    return jnp.moveaxis(out, -1, 1).reshape(B, H, D), lse.reshape(B, H)
