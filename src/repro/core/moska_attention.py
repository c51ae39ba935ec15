"""MoSKA mixture attention: unique-KV partial ⊕ routed shared-KV partial.

This is the per-layer attention used at decode/prefill when a shared corpus
is attached. The unique path is the memory-bound GEMV over the request's own
cache (Fig. 2a left); the shared path is the routed, batched GEMM
(`shared_attention_batched`); the two partials are exact-merged via LSE —
the softmax over the union of the two key sets is recovered exactly
(property-tested in tests/test_moska_core.py).
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax

from repro.configs.base import MoSKAConfig
from repro.core import router as router_lib
from repro.core import shared_attention as sa
from repro.kernels.decode_attn import decode_attention
from repro.models import layers as L


class MoskaLayerContext(NamedTuple):
    """Per-layer shared store slices + routing, computed once per step."""
    k: jax.Array                         # (E, KH, C, D)
    v: jax.Array                         # (E, KH, C, D)
    routing: router_lib.Routing


def route_layer(q_pooled: jax.Array, emb: jax.Array,
                cfg: MoSKAConfig) -> router_lib.Routing:
    return router_lib.route(q_pooled, emb, cfg.top_k_chunks)


def moska_decode_attention(
    q: jax.Array,                        # (B, H, D) one token per request
    k_cache: jax.Array,                  # (L, B, S, KH·D) unique cache
    v_cache: jax.Array,
    kv_len: jax.Array,                   # (B,)
    ctx: Optional[MoskaLayerContext],
    cfg: MoSKAConfig,
    *,
    layer=0,                             # int32 scalar: the layer to read
    window: int = 0,
    kernel: Optional[str] = None,
) -> Tuple[jax.Array, Optional[sa.DispatchStats]]:
    """Returns merged attention output (B, H, D) and the shared path's
    dispatch stats (None without a store). The unique path is the Pallas
    kernel ``kernels.decode_attn``, which reads layer ``layer`` of the
    stacked cache in place; ``kernel`` picks the shared path's."""
    with jax.named_scope("unique_attn"):
        o_u, lse_u = decode_attention(q, k_cache, v_cache, kv_len, layer,
                                      window=window)
    if ctx is None or not cfg.enabled:
        return o_u, None
    with jax.named_scope("shared_dispatch_gemm"):
        part = sa.shared_attention_batched(
            q[:, None], ctx.k, ctx.v, ctx.routing,
            capacity_factor=cfg.query_capacity_factor, kernel=kernel)
    o_s = part.out[:, 0]                 # (B, H, D)
    lse_s = part.lse[:, 0]               # (B, H)
    with jax.named_scope("lse_merge"):
        out, _ = L.merge_partial_attention([o_u, o_s], [lse_u, lse_s])
    return out, part.stats


def moska_prefill_attention(
    q: jax.Array,                        # (B, S, H, D)
    k: jax.Array,                        # (B, S, KH, D) fresh unique keys
    v: jax.Array,
    ctx: Optional[MoskaLayerContext],
    cfg: MoSKAConfig,
    *,
    q_offset: int = 0,
    window: int = 0,
    route_block: int = 128,
    kernel: Optional[str] = None,
) -> Tuple[jax.Array, Optional[sa.DispatchStats]]:
    """Prefill: causal attention over the unique prefix, plus routed shared
    attention for every query block when a shared corpus is attached.
    Returns the output and the dispatch stats (None without a store)."""
    with jax.named_scope("unique_attn"):
        o_u, lse_u = L.flash_attention(q, k, v, causal=True,
                                       q_offset=q_offset, kv_offset=q_offset,
                                       window=window, return_lse=True)
    if ctx is None or not cfg.enabled:
        return o_u, None
    B, S, H, D = q.shape
    nb = S // route_block
    # (B*nb) groups of route_block queries
    qg = q.reshape(B * nb, route_block, H, D)
    with jax.named_scope("shared_dispatch_gemm"):
        part = sa.shared_attention_batched(
            qg, ctx.k, ctx.v, ctx.routing,
            capacity_factor=cfg.query_capacity_factor, kernel=kernel)
    o_s = part.out.reshape(B, S, H, D)
    lse_s = part.lse.reshape(B, S, H)
    with jax.named_scope("lse_merge"):
        out, _ = L.merge_partial_attention([o_u, o_s], [lse_u, lse_s])
    return out, part.stats
