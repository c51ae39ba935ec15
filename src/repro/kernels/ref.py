"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth the kernels are property-tested against
(tests/test_kernels.py sweeps shapes/dtypes with assert_allclose).
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def shared_chunk_attention_ref(qd: jax.Array, k: jax.Array, v: jax.Array,
                               qmask: jax.Array
                               ) -> Tuple[jax.Array, jax.Array]:
    """The batched per-chunk GEMM attention (paper Fig. 2a).

    qd: (E, cap, H, D) dispatched queries; k/v: (E, KH, C, D);
    qmask: (E, cap) bool. Non-causal. Returns (out (E,cap,H,D),
    lse (E,cap,H) fp32; -inf rows where qmask is False).
    """
    E, cap, H, D = qd.shape
    KH = k.shape[1]
    G = H // KH
    scale = 1.0 / math.sqrt(D)
    qg = qd.reshape(E, cap, KH, G, D)
    s = jnp.einsum("eckgd,eksd->eckgs", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("eckgs,eksd->eckgd", p, v.astype(jnp.float32))
    o = o / jnp.maximum(l, 1e-37)[..., None]
    lse = m + jnp.log(jnp.maximum(l, 1e-37))
    lse = jnp.where(qmask[:, :, None, None], lse, NEG_INF)
    out = jnp.where(qmask[:, :, None, None, None], o, 0.0)
    return (out.reshape(E, cap, H, D).astype(qd.dtype),
            lse.reshape(E, cap, H))


def decode_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                         kv_len: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Unique-KV decode GEMV. q: (B, H, D); k/v: (B, S, KH, D);
    kv_len: (B,). Returns (out (B,H,D), lse (B,H) fp32)."""
    B, H, D = q.shape
    S, KH = k.shape[1], k.shape[2]
    G = H // KH
    scale = 1.0 / math.sqrt(D)
    qg = q.reshape(B, KH, G, D)
    s = jnp.einsum("bkgd,bskd->bkgs", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    mask = jnp.arange(S)[None] < kv_len[:, None]
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p, v.astype(jnp.float32))
    o = o / jnp.maximum(l, 1e-37)[..., None]
    lse = m + jnp.log(jnp.maximum(l, 1e-37))
    return o.reshape(B, H, D).astype(q.dtype), lse.reshape(B, H)


def lse_merge_ref(outs: jax.Array, lses: jax.Array
                  ) -> Tuple[jax.Array, jax.Array]:
    """Merge P partial attentions. outs: (P, N, H, D); lses: (P, N, H).
    Exact: equals softmax over the union of key sets."""
    lses = lses.astype(jnp.float32)
    m = jnp.max(lses, axis=0)
    w = jnp.exp(lses - m[None])
    denom = jnp.sum(w, axis=0)
    out = jnp.sum(outs.astype(jnp.float32) * w[..., None], axis=0)
    out = out / jnp.maximum(denom, 1e-37)[..., None]
    lse = jnp.where(denom > 0, m + jnp.log(jnp.maximum(denom, 1e-37)),
                    NEG_INF)
    return out.astype(outs.dtype), lse


def router_scores_ref(q: jax.Array, emb: jax.Array) -> jax.Array:
    """q: (G, H, D); emb: (E, KH, D) -> (G, E) fp32 relevance scores."""
    G, H, D = q.shape
    E, KH, _ = emb.shape
    g = H // KH
    qg = q.reshape(G, KH, g, D).astype(jnp.float32)
    return jnp.einsum("gkhd,ekd->ge", qg,
                      emb.astype(jnp.float32)) / math.sqrt(D)
