"""Unique (per-request) KV cache — the paper's 'Unique KV' pool.

Layout is layer-stacked and lane-dense: k/v (L, B, S, KH·D), kv head ``h``
in lanes ``[h·D, (h+1)·D)``, lengths (B,). The TPU keeps KH·D (a multiple
of 128 in the served configurations) on its 128-wide lanes unpadded, which
a trailing (KH, D) with D = 64 would pad to 128; the decode step carries
the whole stack through its layer scan, writes one row per request and
layer, and ``kernels.decode_attn`` reads each layer where it lies. Sharded
batch-major at serve time (each device owns its requests = the Unique-KV
node of Fig. 3).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class KVCache(NamedTuple):
    k: jax.Array          # (L, B, S, KH·D)
    v: jax.Array          # (L, B, S, KH·D)
    length: jax.Array     # (B,) int32 — valid tokens in *this buffer*
    offset: jax.Array     # (B,) int32 — absolute position of buffer slot 0
                          # (= shared-corpus length when a store precedes it)

    @property
    def max_seq(self) -> int:
        return self.k.shape[2]

    @property
    def positions(self) -> jax.Array:
        """Absolute position of the next token per request."""
        return self.offset + self.length


def init_kv_cache(num_layers: int, batch: int, max_seq: int, kv_heads: int,
                  head_dim: int, dtype=jnp.bfloat16) -> KVCache:
    shape = (num_layers, batch, max_seq, kv_heads * head_dim)
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
                   jnp.zeros((batch,), jnp.int32),
                   jnp.zeros((batch,), jnp.int32))


def abstract_kv_cache(num_layers: int, batch: int, max_seq: int,
                      kv_heads: int, head_dim: int,
                      dtype=jnp.bfloat16) -> KVCache:
    shape = (num_layers, batch, max_seq, kv_heads * head_dim)
    sds = jax.ShapeDtypeStruct
    return KVCache(sds(shape, dtype), sds(shape, dtype),
                   sds((batch,), jnp.int32), sds((batch,), jnp.int32))


def write_slot_prefix(cache: KVCache, slot_cache: KVCache, slot,
                      true_len=None) -> KVCache:
    """Write a prefilled 1-batch cache into batch slot ``slot`` in place.

    The donation-friendly per-slot admission write: jit the caller with
    ``donate_argnums`` on ``cache`` and XLA updates the batch cache buffer
    without copying the other ``B - 1`` slots (vs. the full-cache merge of
    a ``tree_map``-style copy).

    ``slot_cache`` holds a (L, 1, S_new, KH·D) prefix with S_new <=
    cache.max_seq (S_new may be a padded prefill bucket). ``true_len``
    (traced scalar ok), when given, is the real prompt length: positions
    >= true_len inside the prefix are zeroed and the slot length is set to
    ``true_len``, so a reused slot never leaks stale or pad KV beyond the
    new prompt. The slot tail beyond S_new is always zeroed.
    """
    S, S_new = cache.max_seq, slot_cache.max_seq
    if S_new > S:
        raise ValueError(f"slot prefix length {S_new} > cache max_seq {S}")
    slot = jnp.asarray(slot, jnp.int32)

    def wr(dst, src):
        src = src.astype(dst.dtype)
        if true_len is not None:
            valid = jnp.arange(S_new) < true_len
            src = jnp.where(valid[:, None], src, jnp.zeros((), dst.dtype))
        if S > S_new:
            pad = jnp.zeros(src.shape[:2] + (S - S_new,) + src.shape[3:],
                            dst.dtype)
            src = jnp.concatenate([src, pad], axis=2)
        return jax.lax.dynamic_update_slice(dst, src, (0, slot, 0, 0))

    length = (slot_cache.length[0] if true_len is None
              else jnp.asarray(true_len, jnp.int32))
    return KVCache(wr(cache.k, slot_cache.k), wr(cache.v, slot_cache.v),
                   cache.length.at[slot].set(length),
                   cache.offset.at[slot].set(slot_cache.offset[0]))


def read_slot(cache: KVCache, slot: int) -> KVCache:
    """1-batch view of slot ``slot`` (tests / debugging)."""
    return KVCache(cache.k[:, slot:slot + 1], cache.v[:, slot:slot + 1],
                   cache.length[slot:slot + 1], cache.offset[slot:slot + 1])


def write_prefix(k_layer: jax.Array, v_layer: jax.Array, new_k: jax.Array,
                 new_v: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Write a full prefix (B, S_new, KH, D) at position 0 (prefill) into
    a (B, S, KH·D) lane-dense layer, or a (B, S, KH, D) one."""
    def wr(layer, new):
        new = new.reshape(new.shape[:2] + layer.shape[2:]).astype(layer.dtype)
        return jax.lax.dynamic_update_slice_in_dim(layer, new, 0, axis=1)
    return wr(k_layer, new_k), wr(v_layer, new_v)


def append_token(k_layer: jax.Array, v_layer: jax.Array, new_k: jax.Array,
                 new_v: jax.Array, lengths: jax.Array
                 ) -> tuple[jax.Array, jax.Array]:
    """Append one token per request at its current length.

    k_layer: (B, S, KH, D); new_k: (B, KH, D); lengths: (B,). A length
    outside the buffer writes its last row, as ``dynamic_update_slice``
    clamps. One scatter over (slot, position) pairs: a ``vmap`` of
    ``dynamic_update_slice`` compiles for the TPU into a loop over the B
    slots, nine device ops a slot, for K and again for V in every layer.
    """
    B, S = k_layer.shape[:2]
    slot, at = jnp.arange(B), jnp.clip(lengths, 0, S - 1)
    k_layer = k_layer.at[slot, at].set(new_k.astype(k_layer.dtype))
    v_layer = v_layer.at[slot, at].set(new_v.astype(v_layer.dtype))
    return k_layer, v_layer


def append_token_stacked(k: jax.Array, v: jax.Array, layer,
                         new_k: jax.Array, new_v: jax.Array,
                         lengths: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Append one token per request into layer ``layer`` of the stacked
    lane-dense cache, in place when the caller carries it.

    k: (L, B, S, KH·D); new_k: (B, KH, D); lengths: (B,). One scatter of B
    rows at (layer, slot, position), with ``append_token``'s clamp: a
    length outside the buffer writes its last row.
    """
    B, S = k.shape[1:3]
    slot, at = jnp.arange(B), jnp.clip(lengths, 0, S - 1)
    k = k.at[layer, slot, at].set(new_k.reshape(B, -1).astype(k.dtype))
    v = v.at[layer, slot, at].set(new_v.reshape(B, -1).astype(v.dtype))
    return k, v
