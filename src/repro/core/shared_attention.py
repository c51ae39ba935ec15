"""Shared KV Attention (paper §III.A, Fig. 2a) — the core contribution.

N concurrent query groups that routed to the same shared chunk are gathered
into one (N x d) query matrix and attended against the chunk's KV in a
single GEMM, instead of N memory-bound GEMVs. Mechanically this is an
MoE-style capacity dispatch over *queries* (the inverse of expert dispatch):

    route -> dispatch_plan -> scatter Q to (chunks, capacity, ...)
          -> per-chunk flash GEMM (Pallas kernel on TPU)
          -> gather partial (O, LSE) back per (group, k)
          -> LSE-merge over the k selected chunks.

The merged (O, LSE) is later LSE-merged with the unique-KV partial
(`moska_attention.py`), which is exactly the disaggregated combine of
Fig. 3.

Two implementations:
  * ``shared_attention_batched``  — the MoSKA data path (dispatch + GEMM).
  * ``shared_attention_gather_ref`` — per-request gather oracle (what a
    non-batched system does; used for tests and as the GEMV baseline).
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import router as router_lib
from repro.core.shared_kv import SharedKVStore
from repro.sharding import lsc

NEG_INF = -1e30


class DispatchStats(NamedTuple):
    """Dispatch density of one routed call (the paper's compute-bound claim
    hinges on these), returned as step outputs so the engine records them
    on the host with the tokens: no host callback in the program. Scalars
    per call; the layer scans stack them to ``(L,)``."""
    fill: jax.Array        # f32: share of the (chunk, capacity) slots used
    dispatched: jax.Array  # i32: (group, k) routes given a slot
    dropped: jax.Array     # i32: routes that fell off the capacity cliff


class SharedPartial(NamedTuple):
    out: jax.Array     # (G, Q, H, D)
    lse: jax.Array     # (G, Q, H) fp32; -inf where nothing attended
    stats: Optional[DispatchStats] = None   # batched path only


# ---------------------------------------------------------------------------
# per-chunk batched attention (the GEMM) — jnp path; Pallas kernel in
# repro.kernels.shared_chunk_attn is the TPU fast path with identical math.
# ---------------------------------------------------------------------------

def _chunk_batched_attention(qd: jax.Array, k: jax.Array, v: jax.Array,
                             qmask: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """qd: (E, cap, Q, H, D) dispatched queries; k/v: (E, KH, C, D);
    qmask: (E, cap) validity. Non-causal (corpus precedes all queries).

    Returns out (E, cap, Q, H, D), lse (E, cap, Q, H) fp32.
    """
    E, cap, Q, H, D = qd.shape
    KH = k.shape[1]
    G = H // KH
    scale = 1.0 / math.sqrt(D)
    qg = qd.reshape(E, cap, Q, KH, G, D)
    # kv-head-major (E, KH, cap, Q, G, ...) intermediates, like the store
    s = jnp.einsum("ecqkgd,eksd->ekcqgs", qg, k,
                   preferred_element_type=jnp.float32) * scale
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("ekcqgs,eksd->ekcqgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    o = o / jnp.maximum(l, 1e-37)[..., None]
    lse = m + jnp.log(jnp.maximum(l, 1e-37))
    out = o.transpose(0, 2, 3, 1, 4, 5).reshape(E, cap, Q, H, D)
    lse = lse.transpose(0, 2, 3, 1, 4).reshape(E, cap, Q, H)
    lse = jnp.where(qmask[:, :, None, None], lse, NEG_INF)
    return out.astype(qd.dtype), lse


# ---------------------------------------------------------------------------
# the MoSKA path
# ---------------------------------------------------------------------------

def shared_attention_batched(
    q: jax.Array,                  # (G, Q, H, D) query groups (Q=1 decode)
    layer_store_k: jax.Array,      # (E, KH, C, D)
    layer_store_v: jax.Array,      # (E, KH, C, D)
    routing: router_lib.Routing,
    *,
    capacity: Optional[int] = None,
    capacity_factor: float = 2.0,
    kernel: Optional[str] = None,  # None|'jnp'|'pallas'
    block_c: Optional[int] = None,  # kv-tile size for the pallas kernel
) -> SharedPartial:
    """Batched Shared KV Attention over routed chunks."""
    G, Q, H, D = q.shape
    E = layer_store_k.shape[0]
    K = routing.chunk_ids.shape[1]
    if capacity is None:
        capacity = router_lib.required_capacity(G, K, E, capacity_factor)
    capacity = min(capacity, G * K)

    flat, pos, keep = router_lib.dispatch_plan(routing.chunk_ids, E, capacity)
    # repeat each group's queries K times (request-major slot order)
    q_slots = jnp.repeat(q, K, axis=0)                       # (G*K, Q, H, D)
    drop_pos = jnp.where(keep, pos, capacity)                # OOB => dropped
    qd = jnp.zeros((E, capacity, Q, H, D), q.dtype)
    qd = qd.at[flat, drop_pos].set(q_slots, mode="drop")
    qd = lsc(qd, "chunks", None, None, "heads", None)
    qmask = jnp.zeros((E, capacity), bool).at[flat, drop_pos].set(
        keep, mode="drop")
    # each kept route holds one distinct (chunk, slot): qmask has `sent`
    # set entries, so its mean is sent / (E * capacity)
    sent = jnp.sum(keep, dtype=jnp.int32)
    stats = DispatchStats(sent / (E * capacity), sent, G * K - sent)

    if kernel == "pallas":
        from repro.kernels.shared_chunk_attn import shared_chunk_attention
        # kernel takes (E, cap, H, D): fold the per-group query dim into cap
        qd_k = qd.reshape(E, capacity * Q, H, D)
        qm_k = jnp.repeat(qmask, Q, axis=1)
        kern_kwargs = {} if block_c is None else {"block_c": block_c}
        od, lsed = shared_chunk_attention(qd_k, layer_store_k,
                                          layer_store_v, qm_k, **kern_kwargs)
        od = od.reshape(E, capacity, Q, H, D)
        lsed = lsed.reshape(E, capacity, Q, H)
    else:
        od, lsed = _chunk_batched_attention(qd, layer_store_k, layer_store_v,
                                            qmask)
    # pin the per-chunk GEMM results to the chunk sharding: without this,
    # the multi-pod partitioner replicates the GEMM (gathering the whole
    # store per layer — §Perf multi-pod note)
    od = lsc(od, "chunks", None, None, "heads", None)
    lsed = lsc(lsed, "chunks", None, None, "heads")

    # gather partials back to (G, K, Q, H, ...)
    o_bk = od.at[flat, drop_pos].get(mode="fill", fill_value=0.0)
    l_bk = lsed.at[flat, drop_pos].get(mode="fill", fill_value=NEG_INF)
    l_bk = jnp.where(keep[:, None, None], l_bk, NEG_INF)
    o_bk = o_bk.reshape(G, K, Q, H, D)
    l_bk = l_bk.reshape(G, K, Q, H)

    # LSE-merge over the K selected chunks
    m = jnp.max(l_bk, axis=1)                                # (G, Q, H)
    w = jnp.exp(l_bk - m[:, None])
    denom = jnp.sum(w, axis=1)
    out = jnp.sum(o_bk.astype(jnp.float32) * w[..., None], axis=1)
    out = out / jnp.maximum(denom, 1e-37)[..., None]
    lse = m + jnp.log(jnp.maximum(denom, 1e-37))
    lse = jnp.where(denom > 0, lse, NEG_INF)
    return SharedPartial(out.astype(q.dtype), lse, stats)


# ---------------------------------------------------------------------------
# non-batched oracle / baseline (per-request gather => GEMV-shaped)
# ---------------------------------------------------------------------------

def shared_attention_gather_ref(
    q: jax.Array,                  # (G, Q, H, D)
    layer_store_k: jax.Array,      # (E, KH, C, D)
    layer_store_v: jax.Array,
    routing: router_lib.Routing,
) -> SharedPartial:
    """Per-request chunk gather + attention. Semantically identical to the
    batched path when no capacity drops occur; memory-bound (each request
    re-reads its chunks) — this is the baseline MoSKA's GEMM batching beats.
    """
    G, Q, H, D = q.shape
    E, KH, C, _ = layer_store_k.shape
    K = routing.chunk_ids.shape[1]
    scale = 1.0 / math.sqrt(D)
    ksel = layer_store_k[routing.chunk_ids]                  # (G, K, KH, C, D)
    vsel = layer_store_v[routing.chunk_ids]
    ksel = ksel.transpose(0, 2, 1, 3, 4).reshape(G, KH, K * C, D)
    vsel = vsel.transpose(0, 2, 1, 3, 4).reshape(G, KH, K * C, D)
    qg = q.reshape(G, Q, KH, H // KH, D)
    s = jnp.einsum("gqkhd,gksd->gqkhs", qg, ksel,
                   preferred_element_type=jnp.float32) * scale
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("gqkhs,gksd->gqkhd", p.astype(vsel.dtype), vsel,
                   preferred_element_type=jnp.float32)
    o = o / jnp.maximum(l, 1e-37)[..., None]
    lse = (m + jnp.log(jnp.maximum(l, 1e-37))).reshape(G, Q, H)
    return SharedPartial(o.reshape(G, Q, H, D).astype(q.dtype), lse)
