"""Disaggregated execution of MoSKA attention (paper §III.C, Fig. 3),
rendered JAX-native (DESIGN.md §3).

TPU pods are homogeneous, so the paper's two *node types* become two
*sharding regimes* on one mesh:

  Unique-KV pool   — KV caches sharded batch-major over (pod, data): each
                     device runs the memory-bound GEMV for its own requests
                     and co-locates the FFN (exactly Fig. 3 top).
  Shared-KV pool   — the chunk store sharded chunk-major over (pod, data):
                     each device owns a chunk subset and serves *all*
                     requests' queries for those chunks (Fig. 3 bottom).

The collective schedule made explicit by ``shard_map`` here:

  all-gather(q over chunk axis)        # queries travel to chunk owners
  local routed batched GEMM            # Shared KV Attention on local chunks
  all-reduce LSE-merge (max, then sum) # the disaggregated combine

which is also exactly what pjit emits from the sharding constraints in
``shared_attention_batched`` — this module is the explicit/schedulable
variant used by the serving engine and §Perf experiments.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import MoSKAConfig
from repro.core import router as router_lib
from repro.core import shared_attention as sa

NEG_INF = -1e30


def disaggregated_shared_attention(
    q: jax.Array,              # (B, H, D) decode queries, batch-sharded
    store_k: jax.Array,        # (E, KH, C, D) chunk-sharded over axis
    store_v: jax.Array,
    emb: jax.Array,            # (E, KH, D) chunk-sharded
    cfg: MoSKAConfig,
    mesh: Mesh,
    *,
    chunk_axis: str | Tuple[str, ...] = "data",
    batch_axis: Optional[str | Tuple[str, ...]] = None,
    kernel: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Returns the merged shared partial (out (B,H,D), lse (B,H)) with the
    explicit disaggregated collective schedule."""
    axes = (chunk_axis,) if isinstance(chunk_axis, str) else tuple(chunk_axis)

    def local_fn(q_l, k_l, v_l, emb_l):
        # q_l: (B, H, D) replicated over the chunk axis (all-gathered by the
        # in_spec); k_l/v_l/emb_l: this device's chunk shard.
        E_local = k_l.shape[0]
        topk = min(cfg.top_k_chunks, E_local)
        # route against LOCAL chunks: each owner picks its best-k local
        # chunks per query; the global merge weights partials by true LSE,
        # so locally-routed partials compose exactly like global top-(k*n)
        # routing restricted to per-shard winners (documented deviation:
        # per-shard top-k, the standard distributed-MoE approximation).
        routing = router_lib.route(q_l, emb_l, topk)
        part = sa.shared_attention_batched(
            q_l[:, None], k_l, v_l, routing,
            capacity_factor=cfg.query_capacity_factor, kernel=kernel)
        o_l = part.out[:, 0].astype(jnp.float32)   # (B, H, D)
        lse_l = part.lse[:, 0]                     # (B, H)
        # --- the disaggregated combine: exact LSE merge across owners ---
        m = lse_l
        for ax in axes:
            m = jax.lax.pmax(m, ax)
        w = jnp.where(lse_l > NEG_INF / 2, jnp.exp(lse_l - m), 0.0)
        num = o_l * w[..., None]
        den = w
        for ax in axes:
            num = jax.lax.psum(num, ax)
            den = jax.lax.psum(den, ax)
        out = num / jnp.maximum(den, 1e-37)[..., None]
        lse = jnp.where(den > 0, m + jnp.log(jnp.maximum(den, 1e-37)),
                        NEG_INF)
        return out.astype(q_l.dtype), lse

    cspec = P(chunk_axis)
    # The pmax/psum combine makes both outputs identical on every chunk
    # owner. Varying-axes checking stays off: a Pallas kernel
    # (kernel="pallas") cannot be traced under it, neither compiled (its
    # out_shape carries no vma) nor interpreted (JAX 0.9 rejects the
    # interpreter's slices of varying operands).
    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(batch_axis), cspec, cspec, cspec),
        out_specs=(P(batch_axis), P(batch_axis)),
        check_vma=False,
    )(q, store_k, store_v, emb)
