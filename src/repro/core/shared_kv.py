"""Shared KV chunk store — the persistent, massively-reused corpus KV.

The paper (§III.A/B) manages the shared context as pre-computed,
position-annotated KV chunks ("experts"). The store is a pytree so it
shards: the chunk axis is the paper's *Shared KV node pool* and is sharded
over the ``data`` (and ``pod``) mesh axes at serve time (DESIGN.md §5).

Layout (stacked over layers so the decoder `lax.scan` consumes one slice
per layer):
    k, v : (L, n_chunks, kv_heads, chunk_size, head_dim)   post-RoPE keys
    emb  : (L, n_chunks, kv_heads, head_dim)               router embeddings

Keys and values are laid out per kv head, so one kv head's tile of one
chunk is a contiguous (chunk tile, head_dim) block — the block shape the
TPU kernel ``kernels.shared_chunk_attn`` needs.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig


class SharedKVStore(NamedTuple):
    k: jax.Array            # (L, E, KH, C, D)  bf16, or int8 when quantized
    v: jax.Array            # (L, E, KH, C, D)
    emb: jax.Array          # (L, E, KH, D) mean-key chunk embeddings
    # absolute corpus position of the first token of each chunk; chunk i is
    # contiguous. positional=False => chunk-local positions (Universal MoSKA)
    chunk_positions: jax.Array  # (E,) int32
    # int8 quantization scales (None => unquantized). Per (layer, chunk,
    # kv_head, token): the TPU analogue of the paper's FP8 KV (v5e has no
    # FP8; int8 gives the same capacity/bandwidth halving).
    k_scale: Optional[jax.Array] = None   # (L, E, KH, C) f32
    v_scale: Optional[jax.Array] = None

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    def dequantize_layer(self, i):
        """Return (k, v) of layer i in compute dtype."""
        if not self.quantized:
            return self.k[i], self.v[i]
        k = self.k[i].astype(jnp.bfloat16) * \
            self.k_scale[i][..., None].astype(jnp.bfloat16)
        v = self.v[i].astype(jnp.bfloat16) * \
            self.v_scale[i][..., None].astype(jnp.bfloat16)
        return k, v

    @property
    def num_layers(self) -> int:
        return self.k.shape[0]

    @property
    def num_chunks(self) -> int:
        return self.k.shape[1]

    @property
    def chunk_size(self) -> int:
        return self.k.shape[3]

    @property
    def total_tokens(self) -> int:
        return self.num_chunks * self.chunk_size

    def layer(self, i) -> "SharedKVStore":
        return SharedKVStore(self.k[i], self.v[i], self.emb[i],
                             self.chunk_positions)


def chunk_embeddings(k_chunks: jax.Array) -> jax.Array:
    """Training-free router embeddings: mean key per chunk (LongHeads/MoBA).

    k_chunks: (..., E, KH, C, D) -> (..., E, KH, D)
    """
    return jnp.mean(k_chunks.astype(jnp.float32), axis=-2).astype(
        k_chunks.dtype)


def _quantize(x: jax.Array):
    """(..., D) -> int8 values + per-row f32 scale."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def build_store(k: jax.Array, v: jax.Array, chunk_size: int,
                start_position: int = 0,
                quantize: bool = False,
                head_dim: Optional[int] = None) -> SharedKVStore:
    """Chunk a corpus KV into a SharedKVStore.

    k/v: (L, S, KH, D), or the slotted cache's lane-dense (L, S, KH·D) with
    ``head_dim`` = D, which is split into heads here, once. Keys are
    expected post-RoPE at absolute corpus positions
    ``start_position + [0, S)``; S must be a multiple of chunk_size.
    ``quantize=True`` stores int8 KV + per-(token, head) f32 scales
    (capacity/bandwidth parity with the paper's FP8 assumption).
    """
    if k.ndim == 3:
        if head_dim is None:
            raise ValueError("a lane-dense (L, S, KH·D) corpus KV needs "
                             "head_dim to be split into heads")
        k = k.reshape(k.shape[:2] + (-1, head_dim))
        v = v.reshape(v.shape[:2] + (-1, head_dim))
    L, S, KH, D = k.shape
    if S % chunk_size:
        raise ValueError(f"corpus length {S} not a multiple of chunk_size "
                         f"{chunk_size}")
    E = S // chunk_size
    kc = k.reshape(L, E, chunk_size, KH, D).transpose(0, 1, 3, 2, 4)
    vc = v.reshape(L, E, chunk_size, KH, D).transpose(0, 1, 3, 2, 4)
    emb = chunk_embeddings(kc)
    pos = start_position + jnp.arange(E, dtype=jnp.int32) * chunk_size
    if not quantize:
        return SharedKVStore(kc, vc, emb, pos)
    kq, ks = _quantize(kc)
    vq, vs = _quantize(vc)
    return SharedKVStore(kq, vq, emb, pos, ks, vs)


def abstract_store(cfg: ModelConfig, shared_tokens: int,
                   dtype=jnp.bfloat16) -> SharedKVStore:
    """ShapeDtypeStruct stand-in for dry-runs (no allocation)."""
    C = cfg.moska.chunk_size
    E = shared_tokens // C
    L = cfg.num_attention_layers
    KH, D = cfg.num_kv_heads, cfg.head_dim
    sds = jax.ShapeDtypeStruct
    quant = cfg.moska.kv_quant == "int8"
    return SharedKVStore(
        k=sds((L, E, KH, C, D), jnp.int8 if quant else dtype),
        v=sds((L, E, KH, C, D), jnp.int8 if quant else dtype),
        emb=sds((L, E, KH, D), dtype),
        chunk_positions=sds((E,), jnp.int32),
        k_scale=sds((L, E, KH, C), jnp.float32) if quant else None,
        v_scale=sds((L, E, KH, C), jnp.float32) if quant else None,
    )
