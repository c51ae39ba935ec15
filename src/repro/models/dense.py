"""Dense / GQA decoder — covers the dense, vlm, and moe families.

Pre-norm transformer with RoPE, GQA attention, SwiGLU FFN (or
capacity-dispatch MoE), layer stack folded with ``jax.lax.scan`` so HLO size
is depth-independent (mandatory for the 88-layer Mistral-Large dry-run).

MoSKA integration: at prefill/decode, when a ``SharedKVStore`` is attached,
each layer routes its queries over the layer's shared chunks and merges the
batched shared partial with the unique partial (core/moska_attention.py).

VLM (internvl2): the stub vision frontend delivers patch embeddings
(B, P, d_model) which are prepended to the token embeddings; loss masks the
patch positions. No cross-attention (InternVL2 is decoder-inline).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import moska_attention as MA
from repro.core import router as router_lib
from repro.core import shared_attention as sa
from repro.core.shared_kv import SharedKVStore
from repro.kvcache.cache import (KVCache, append_token_stacked,
                                 write_prefix)
from repro.kvcache.paged import PagedKVCache, append_layer, gather_layer
from repro.models import layers as L
from repro.models import moe as moe_lib
from repro.sharding import lsc

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _layer_init(cfg: ModelConfig, key) -> Params:
    ka, km, kd = jax.random.split(key, 3)
    dtype = jnp.dtype(cfg.dtype)
    p: Params = {
        "ln1": {"scale": jnp.zeros((cfg.d_model,), dtype)},
        "ln2": {"scale": jnp.zeros((cfg.d_model,), dtype)},
        "attn": L.attn_init(ka, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                            cfg.head_dim, cfg.qkv_bias, dtype),
    }
    if cfg.moe.enabled:
        p["moe"] = moe_lib.moe_init(km, cfg.d_model, cfg.d_ff, cfg.moe, dtype)
        if cfg.moe.dense_residual:
            p["mlp"] = L.mlp_init(kd, cfg.d_model, cfg.d_ff, dtype)
    else:
        p["mlp"] = L.mlp_init(km, cfg.d_model, cfg.d_ff, dtype)
    return p


def init_params(cfg: ModelConfig, key) -> Params:
    ke, kl, ku = jax.random.split(key, 3)
    dtype = jnp.dtype(cfg.dtype)
    layer_keys = jax.random.split(kl, cfg.num_layers)
    layers = jax.vmap(partial(_layer_init, cfg))(layer_keys)
    params: Params = {
        "embed": {"embed": jax.random.normal(
            ke, (cfg.vocab_size, cfg.d_model), dtype) / math.sqrt(cfg.d_model)},
        "layers": layers,
        "final_norm": {"scale": jnp.zeros((cfg.d_model,), dtype)},
    }
    if not cfg.tie_embeddings:
        params["unembed"] = {"unembed": jax.random.normal(
            ku, (cfg.vocab_size, cfg.d_model), dtype) / math.sqrt(cfg.d_model)}
    return params


def unembed_matrix(cfg: ModelConfig, params: Params) -> jax.Array:
    if cfg.tie_embeddings or "unembed" not in params:
        return params["embed"]["embed"]
    return params["unembed"]["unembed"]


def _lm_head(cfg: ModelConfig, params: Params, x: jax.Array) -> jax.Array:
    """x: (B, d) last hidden states -> (B, V) float32 logits."""
    with jax.named_scope("lm_head"):
        return jnp.einsum("bd,vd->bv", x, unembed_matrix(cfg, params),
                          preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# layer bodies
# ---------------------------------------------------------------------------

def _ffn(cfg: ModelConfig, lp: Params, x: jax.Array
         ) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (out, moe_aux)."""
    B, S, d = x.shape
    if cfg.moe.enabled:
        y, aux = moe_lib.moe_ffn(x.reshape(B * S, d), lp["moe"], cfg.moe)
        y = y.reshape(B, S, d)
        if cfg.moe.dense_residual:
            y = y + L.swiglu_mlp(x, lp["mlp"])
        return y, aux
    return L.swiglu_mlp(x, lp["mlp"]), jnp.zeros((), jnp.float32)


def _attn_out_proj(o: jax.Array, lp: Params) -> jax.Array:
    """o: (B, S, H, D) or (B, H, D) -> project back to d_model."""
    flat = o.reshape(*o.shape[:-2], -1)
    return jnp.einsum("...h,hd->...d", flat, lp["attn"]["wo"])


def _layer_train(cfg: ModelConfig, x: jax.Array, lp: Params,
                 positions: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence causal layer (train / no-cache forward).

    x: (B, S, d); positions: (S,) or (B, S). Returns (x_out, moe_aux).
    """
    h = L.rms_norm(x, lp["ln1"]["scale"], cfg.rms_eps)
    q, k, v = L.qkv_project(h, lp["attn"], cfg.num_heads, cfg.num_kv_heads,
                            cfg.head_dim)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    q = lsc(q, "batch", "seq", "heads", None)
    k = lsc(k, "batch", "seq", "kv_heads", None)
    v = lsc(v, "batch", "seq", "kv_heads", None)
    o = L.flash_attention(q, k, v, causal=True, window=cfg.attn_window,
                          block_k=cfg.attn_block_k)
    x = lsc(x + _attn_out_proj(o, lp), "batch", "seq_res", None)
    h2 = L.rms_norm(x, lp["ln2"]["scale"], cfg.rms_eps)
    y, aux = _ffn(cfg, lp, h2)
    x = lsc(x + y, "batch", "seq_res", None)
    return x, aux


def _layer_prefill(cfg: ModelConfig, x: jax.Array, lp: Params,
                   positions: jax.Array,
                   kc: jax.Array, vc: jax.Array,
                   shared: Optional[Tuple[jax.Array, jax.Array, jax.Array]],
                   q_offset: jax.Array,
                   true_len: Optional[jax.Array] = None,
                   kernel: Optional[str] = None
                   ) -> Tuple[jax.Array, jax.Array, jax.Array,
                              Optional[sa.DispatchStats]]:
    """Prefill layer: causal attention + cache write + optional MoSKA path.

    ``true_len`` (traced scalar ok): the real prompt length when the
    sequence is right-padded to a prefill bucket. Pad queries are excluded
    from router pooling so routing (and hence every real row's output)
    matches the exact-length program; pad rows themselves produce garbage
    that the caller discards.

    Returns (x_out, new_k_layer, new_v_layer, dispatch stats or None).
    """
    h = L.rms_norm(x, lp["ln1"]["scale"], cfg.rms_eps)
    q, k, v = L.qkv_project(h, lp["attn"], cfg.num_heads, cfg.num_kv_heads,
                            cfg.head_dim)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    q = lsc(q, "batch", "seq", "heads", None)
    kc, vc = write_prefix(kc, vc, k, v)

    stats = None
    if shared is not None and cfg.moska.enabled:
        sk, sv, semb = _shared_layer(shared, x.dtype)
        B, S, H, D = q.shape
        rb = min(128, S)
        nb = S // rb
        with jax.named_scope("shared_route"):
            if true_len is None:
                pooled = jnp.mean(q.reshape(B * nb, rb, H, D), axis=1)
            else:
                valid = (jnp.arange(S) < true_len).astype(q.dtype)  # (S,)
                qs = (q * valid[None, :, None, None]).reshape(B, nb, rb, H,
                                                              D)
                cnt = jnp.maximum(valid.reshape(nb, rb).sum(axis=1), 1.0)
                pooled = (jnp.sum(qs, axis=2) /
                          cnt[None, :, None, None]).reshape(B * nb, H, D)
            routing = router_lib.route(pooled, semb, cfg.moska.top_k_chunks)
        ctx = MA.MoskaLayerContext(sk, sv, routing)
        o, stats = MA.moska_prefill_attention(
            q, k, v, ctx, cfg.moska, q_offset=q_offset,
            window=cfg.attn_window, route_block=rb, kernel=kernel)
    else:
        with jax.named_scope("unique_attn"):
            o = L.flash_attention(q, k, v, causal=True, q_offset=q_offset,
                                  kv_offset=q_offset, window=cfg.attn_window)
    x = x + lsc(_attn_out_proj(o, lp), "batch", "seq", None)
    h2 = L.rms_norm(x, lp["ln2"]["scale"], cfg.rms_eps)
    with jax.named_scope("mlp"):
        y, _ = _ffn(cfg, lp, h2)
    x = x + lsc(y, "batch", "seq", None)
    return x, kc, vc, stats


def _layer_decode(cfg: ModelConfig, x: jax.Array, lp: Params,
                  positions: jax.Array,
                  k: jax.Array, v: jax.Array, layer: jax.Array,
                  lengths: jax.Array,
                  shared: Optional[Tuple[jax.Array, jax.Array, jax.Array]],
                  kernel: Optional[str] = None
                  ) -> Tuple[jax.Array, jax.Array, jax.Array,
                             Optional[sa.DispatchStats]]:
    """Decode layer ``layer``: one token per request.

    x: (B, d); positions: (B,) absolute position of the new token; k/v:
    the whole stacked cache (L, B, S, KH·D), written at one row per
    request and read in place. Returns (x_out, k, v, dispatch stats or
    None).
    """
    B, d = x.shape
    h = L.rms_norm(x, lp["ln1"]["scale"], cfg.rms_eps)
    q, k_new, v_new = L.qkv_project(h[:, None], lp["attn"], cfg.num_heads,
                                    cfg.num_kv_heads, cfg.head_dim)
    q = L.apply_rope(q, positions[:, None], cfg.rope_theta)[:, 0]  # (B,H,D)
    k_new = L.apply_rope(k_new, positions[:, None], cfg.rope_theta)[:, 0]
    q = lsc(q, "batch", "heads", None)
    k, v = append_token_stacked(k, v, layer, k_new, v_new[:, 0], lengths)
    new_len = lengths + 1

    o, stats = _decode_mixture(cfg, x, q, k, v, layer, new_len, shared,
                               kernel)
    x = x + _attn_out_proj(o, lp)
    h2 = L.rms_norm(x, lp["ln2"]["scale"], cfg.rms_eps)
    with jax.named_scope("mlp"):
        y, _ = _ffn(cfg, lp, h2[:, None])
    x = x + y[:, 0]
    return x, k, v, stats


def _decode_mixture(cfg: ModelConfig, x: jax.Array, q: jax.Array,
                    k: jax.Array, v: jax.Array, layer, new_len: jax.Array,
                    shared, kernel: Optional[str]):
    """The decode layer's attention over layer ``layer`` of the stacked
    cache ``k``/``v`` (L, B, S, KH·D); routes ``q`` over the layer's shared
    chunks when a store is attached. Returns (output, stats or None)."""
    ctx = None
    if shared is not None and cfg.moska.enabled:
        sk, sv, semb = _shared_layer(shared, x.dtype)
        with jax.named_scope("shared_route"):
            routing = router_lib.route(q, semb, cfg.moska.top_k_chunks)
        ctx = MA.MoskaLayerContext(sk, sv, routing)
    return MA.moska_decode_attention(q, k, v, new_len, ctx, cfg.moska,
                                     layer=layer, window=cfg.attn_window,
                                     kernel=kernel)


def _layer_decode_paged(cfg: ModelConfig, x: jax.Array, lp: Params,
                        positions: jax.Array,
                        kp: jax.Array, vp: jax.Array,
                        table: jax.Array, lengths: jax.Array,
                        shared: Optional[Tuple[jax.Array, jax.Array,
                                               jax.Array]],
                        kernel: Optional[str] = None
                        ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                   Optional[sa.DispatchStats]]:
    """Paged decode layer: identical math to ``_layer_decode`` but the
    unique KV lives in a block pool.

    kp/vp: (N, bs, KH, D) one layer's physical pages; table: (B, M) block
    tables; lengths: (B,). The new token is scattered into its page, then
    the tables gather a contiguous (B, M*bs, KH, D) view and the *same*
    mixture attention runs on it — when ``M*bs == max_seq`` the attention
    program is shape-identical to the slotted one and (because masked
    positions get exactly-zero softmax weight) the outputs are bitwise
    equal for live slots.
    """
    B, d = x.shape
    h = L.rms_norm(x, lp["ln1"]["scale"], cfg.rms_eps)
    q, k, v = L.qkv_project(h[:, None], lp["attn"], cfg.num_heads,
                            cfg.num_kv_heads, cfg.head_dim)
    q = L.apply_rope(q, positions[:, None], cfg.rope_theta)[:, 0]  # (B,H,D)
    k = L.apply_rope(k, positions[:, None], cfg.rope_theta)[:, 0]
    v = v[:, 0]
    q = lsc(q, "batch", "heads", None)
    kp = append_layer(kp, k, table, lengths)
    vp = append_layer(vp, v, table, lengths)
    new_len = lengths + 1
    kc = gather_layer(kp, table)                     # (B, M*bs, KH, D)
    vc = gather_layer(vp, table)
    # the slotted layout's lane-dense stack, of one layer
    kc = kc.reshape(1, *kc.shape[:2], -1)
    vc = vc.reshape(1, *vc.shape[:2], -1)

    o, stats = _decode_mixture(cfg, x, q, kc, vc, 0, new_len, shared,
                               kernel)
    x = x + _attn_out_proj(o, lp)
    h2 = L.rms_norm(x, lp["ln2"]["scale"], cfg.rms_eps)
    with jax.named_scope("mlp"):
        y, _ = _ffn(cfg, lp, h2[:, None])
    x = x + y[:, 0]
    return x, kp, vp, stats


# ---------------------------------------------------------------------------
# full-model forwards (scan over layers)
# ---------------------------------------------------------------------------

def embed_inputs(cfg: ModelConfig, params: Params, tokens: jax.Array,
                 frontend_embeds: Optional[jax.Array] = None) -> jax.Array:
    x = params["embed"]["embed"][tokens]
    if frontend_embeds is not None:
        x = jnp.concatenate([frontend_embeds.astype(x.dtype), x], axis=1)
    return lsc(x, "batch", "seq", None)


def remat_policy(cfg: ModelConfig):
    return {
        "nothing": jax.checkpoint_policies.nothing_saveable,
        "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    }[cfg.remat_policy]


def forward_hidden(cfg: ModelConfig, params: Params, x: jax.Array,
                   positions: jax.Array, *, remat: bool = True
                   ) -> Tuple[jax.Array, jax.Array]:
    """Run the layer stack (train path). Returns (hidden, moe_aux_sum)."""
    body_fn = partial(_layer_train, cfg)
    if remat and cfg.remat_policy != "none":
        body_fn = jax.checkpoint(body_fn, policy=remat_policy(cfg))

    def scan_body(carry, lp):
        x = carry
        x, aux = body_fn(x, lp, positions)
        return x, aux

    x, auxs = jax.lax.scan(scan_body, x, params["layers"])
    x = L.rms_norm(x, params["final_norm"]["scale"], cfg.rms_eps)
    return x, jnp.sum(auxs)


def lm_loss(cfg: ModelConfig, params: Params, hidden: jax.Array,
            targets: jax.Array, mask: jax.Array, *,
            seq_chunk: int = 512) -> jax.Array:
    """Chunked cross-entropy: never materializes (B, S, V) logits.

    hidden: (B, S, d); targets/mask: (B, S). Vocab stays sharded over the
    model axis inside each chunk.
    """
    B, S, d = hidden.shape
    W = unembed_matrix(cfg, params)                          # (V, d)
    seq_chunk = min(seq_chunk, S)
    nck = S // seq_chunk
    rem = S - nck * seq_chunk

    def chunk_loss(h, t, m):
        logits = jnp.einsum("bsd,vd->bsv", h, W,
                            preferred_element_type=jnp.float32)
        logits = lsc(logits, "batch", "seq", "vocab")
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, t[..., None], axis=-1)[..., 0]
        return jnp.sum((lse - ll) * m)

    def body(carry, xs):
        h, t, m = xs
        return carry + chunk_loss(h, t, m), None

    hs = hidden[:, : nck * seq_chunk].reshape(B, nck, seq_chunk, d)
    ts = targets[:, : nck * seq_chunk].reshape(B, nck, seq_chunk)
    ms = mask[:, : nck * seq_chunk].reshape(B, nck, seq_chunk)
    total, _ = jax.lax.scan(
        body, jnp.zeros((), jnp.float32),
        (hs.swapaxes(0, 1), ts.swapaxes(0, 1), ms.swapaxes(0, 1)))
    if rem:
        total = total + chunk_loss(hidden[:, nck * seq_chunk:],
                                   targets[:, nck * seq_chunk:],
                                   mask[:, nck * seq_chunk:])
    return total / jnp.maximum(jnp.sum(mask), 1.0)


def train_loss(cfg: ModelConfig, params: Params, batch: Dict[str, jax.Array],
               *, remat: bool = True) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    tokens = batch["tokens"]
    x = embed_inputs(cfg, params, tokens, batch.get("frontend_embeds"))
    S = x.shape[1]
    positions = jnp.arange(S)
    hidden, aux = forward_hidden(cfg, params, x, positions, remat=remat)
    P = S - tokens.shape[1]  # frontend positions carry no loss
    hidden_txt = hidden[:, P:]
    loss = lm_loss(cfg, params, hidden_txt, batch["targets"], batch["mask"])
    total = loss + aux
    return total, {"ce_loss": loss, "moe_aux": aux}


def _shared_xs(cfg: ModelConfig, store: Optional[SharedKVStore]):
    if store is None or not cfg.moska.enabled:
        return None
    d = {"k": store.k, "v": store.v, "emb": store.emb}
    if store.quantized:
        d["ks"] = store.k_scale
        d["vs"] = store.v_scale
    return d


def _shared_layer(sh, dtype):
    """Per-layer store slices; dequantizes an int8 store to ``dtype``."""
    sk, sv, semb = sh["k"], sh["v"], sh["emb"]
    if "ks" in sh:
        sk = sk.astype(dtype) * sh["ks"][..., None].astype(dtype)
        sv = sv.astype(dtype) * sh["vs"][..., None].astype(dtype)
    return sk, sv, semb


def prefill(cfg: ModelConfig, params: Params, tokens: jax.Array,
            cache: KVCache, store: Optional[SharedKVStore] = None,
            frontend_embeds: Optional[jax.Array] = None,
            start_pos: int = 0,
            true_len: Optional[jax.Array] = None,
            kernel: Optional[str] = None, return_stats: bool = False):
    """Process the unique prefix; returns (last-token logits, filled cache),
    and with ``return_stats`` the store's per-layer ``DispatchStats`` (each
    field ``(L,)``; None without a store) as a third element.

    ``true_len`` (traced scalar ok): real prompt length when ``tokens`` is
    right-padded to a prefill bucket — logits are taken at position
    ``true_len - 1`` and the cache lengths record ``true_len``. Not
    supported together with ``frontend_embeds``. ``kernel`` selects the
    shared-attention implementation (None: jnp; 'pallas').
    """
    if true_len is not None and frontend_embeds is not None:
        raise ValueError("true_len is not supported with frontend_embeds")
    x = embed_inputs(cfg, params, tokens, frontend_embeds)
    B, S, _ = x.shape
    positions = start_pos + jnp.arange(S)
    shared = _shared_xs(cfg, store)

    def scan_body(x, xs):
        lp, kc, vc, sh = xs if shared is not None else (*xs, None)
        x, kc, vc, st = _layer_prefill(cfg, x, lp, positions, kc, vc, sh,
                                       jnp.asarray(start_pos),
                                       true_len=true_len, kernel=kernel)
        return x, (kc, vc, st)

    xs = ((params["layers"], cache.k, cache.v) if shared is None else
          (params["layers"], cache.k, cache.v, shared))
    x, (k_new, v_new, stats) = jax.lax.scan(scan_body, x, xs)
    x = L.rms_norm(x, params["final_norm"]["scale"], cfg.rms_eps)
    if true_len is None:
        x_last = x[:, -1]
        n_valid = jnp.asarray(S, jnp.int32)
    else:
        n_valid = jnp.asarray(true_len, jnp.int32)
        x_last = jax.lax.dynamic_index_in_dim(x, n_valid - 1, axis=1,
                                              keepdims=False)
    logits = _lm_head(cfg, params, x_last)
    lengths = jnp.full((B,), n_valid, jnp.int32)
    offsets = jnp.full((B,), start_pos, jnp.int32)
    out = (logits, KVCache(k_new, v_new, lengths, offsets))
    return (*out, stats) if return_stats else out


def decode_step(cfg: ModelConfig, params: Params, tokens: jax.Array,
                cache: KVCache, store: Optional[SharedKVStore] = None,
                positions: Optional[jax.Array] = None,
                kernel: Optional[str] = None, return_stats: bool = False):
    """One decode step. tokens: (B,). Returns (logits (B, V), new cache),
    and with ``return_stats`` the per-layer ``DispatchStats`` (or None)."""
    x = params["embed"]["embed"][tokens]                     # (B, d)
    x = lsc(x, "batch", None)
    if positions is None:
        positions = cache.positions                          # absolute (RoPE)
    shared = _shared_xs(cfg, store)

    # the cache is loop state, not a scanned input: each layer writes its
    # rows into the stack and attention reads the layer in place, so no
    # layer slab is sliced out, relaid or stacked back
    def scan_body(carry, xs):
        x, k, v = carry
        lp, i, sh = xs if shared is not None else (*xs, None)
        x, k, v, st = _layer_decode(cfg, x, lp, positions, k, v, i,
                                    cache.length, sh, kernel=kernel)
        return (x, k, v), st

    layer_ids = jnp.arange(cache.k.shape[0], dtype=jnp.int32)
    xs = ((params["layers"], layer_ids) if shared is None else
          (params["layers"], layer_ids, shared))
    (x, k_new, v_new), stats = jax.lax.scan(scan_body,
                                            (x, cache.k, cache.v), xs)
    x = L.rms_norm(x, params["final_norm"]["scale"], cfg.rms_eps)
    logits = _lm_head(cfg, params, x)
    out = (logits, KVCache(k_new, v_new, cache.length + 1, cache.offset))
    return (*out, stats) if return_stats else out


def decode_step_paged(cfg: ModelConfig, params: Params, tokens: jax.Array,
                      pool: PagedKVCache, table: jax.Array,
                      lengths: jax.Array, offsets: jax.Array,
                      store: Optional[SharedKVStore] = None,
                      kernel: Optional[str] = None,
                      return_stats: bool = False):
    """One decode step over the paged unique-KV pool.

    tokens: (B,); pool: physical pages (L, N, bs, KH, D); table: (B, M)
    int32 block tables; lengths/offsets: (B,) — the host-side mirror of the
    slotted cache's length/offset vectors (``SlotTables``). Returns
    (logits (B, V), new pool), plus the per-layer ``DispatchStats`` with
    ``return_stats``. The caller advances lengths (``tick``).
    """
    x = params["embed"]["embed"][tokens]                     # (B, d)
    x = lsc(x, "batch", None)
    positions = offsets + lengths                            # absolute (RoPE)
    shared = _shared_xs(cfg, store)

    def scan_body(x, xs):
        lp, kp, vp, sh = xs if shared is not None else (*xs, None)
        x, kp, vp, st = _layer_decode_paged(cfg, x, lp, positions, kp, vp,
                                            table, lengths, sh,
                                            kernel=kernel)
        return x, (kp, vp, st)

    xs = ((params["layers"], pool.k, pool.v) if shared is None else
          (params["layers"], pool.k, pool.v, shared))
    x, (k_new, v_new, stats) = jax.lax.scan(scan_body, x, xs)
    x = L.rms_norm(x, params["final_norm"]["scale"], cfg.rms_eps)
    logits = _lm_head(cfg, params, x)
    out = (logits, PagedKVCache(k_new, v_new))
    return (*out, stats) if return_stats else out


# ---------------------------------------------------------------------------
# chunked prefill (long prompts, paged serving path)
# ---------------------------------------------------------------------------

def _layer_prefill_chunk(cfg: ModelConfig, x: jax.Array, lp: Params,
                         positions: jax.Array,
                         kc: jax.Array, vc: jax.Array,
                         base: jax.Array, chunk_len: jax.Array,
                         shared, start_pos: jax.Array,
                         kernel: Optional[str] = None
                         ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                    Optional[sa.DispatchStats]]:
    """One chunk of a long prompt against the growing context view.

    x: (B, C, d) chunk activations (right-padded; ``chunk_len`` real);
    kc/vc: (B, V, KH·D) scratch context holding ``base`` earlier tokens;
    the chunk's fresh keys are written at ``base`` and causal attention
    runs over the whole view with ``kv_len = base + chunk_len`` masking.
    Returns (x_out, kc, vc, dispatch stats or None).
    """
    h = L.rms_norm(x, lp["ln1"]["scale"], cfg.rms_eps)
    q, k, v = L.qkv_project(h, lp["attn"], cfg.num_heads, cfg.num_kv_heads,
                            cfg.head_dim)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    q = lsc(q, "batch", "seq", "heads", None)
    B, C = k.shape[:2]
    kc = jax.lax.dynamic_update_slice_in_dim(
        kc, k.reshape(B, C, -1).astype(kc.dtype), base, axis=1)
    vc = jax.lax.dynamic_update_slice_in_dim(
        vc, v.reshape(B, C, -1).astype(vc.dtype), base, axis=1)
    kv_valid = base + chunk_len
    k_ctx = kc.reshape(kc.shape[:2] + k.shape[2:])           # (B, V, KH, D)
    v_ctx = vc.reshape(vc.shape[:2] + v.shape[2:])

    stats = None
    if shared is not None and cfg.moska.enabled:
        sk, sv, semb = _shared_layer(shared, x.dtype)
        B, C, H, D = q.shape
        rb = min(128, C)
        nb = C // rb
        with jax.named_scope("shared_route"):
            valid = (jnp.arange(C) < chunk_len).astype(q.dtype)    # (C,)
            qs = (q * valid[None, :, None, None]).reshape(B, nb, rb, H, D)
            cnt = jnp.maximum(valid.reshape(nb, rb).sum(axis=1), 1.0)
            pooled = (jnp.sum(qs, axis=2) /
                      cnt[None, :, None, None]).reshape(B * nb, H, D)
            routing = router_lib.route(pooled, semb, cfg.moska.top_k_chunks)
        with jax.named_scope("unique_attn"):
            o_u, lse_u = L.flash_attention(
                q, k_ctx, v_ctx, causal=True, q_offset=start_pos + base,
                kv_offset=start_pos, kv_len=kv_valid, window=cfg.attn_window,
                return_lse=True)
        with jax.named_scope("shared_dispatch_gemm"):
            part = sa.shared_attention_batched(
                q.reshape(B * nb, rb, H, D), sk, sv, routing,
                capacity_factor=cfg.moska.query_capacity_factor,
                kernel=kernel)
        o_s = part.out.reshape(B, C, H, D)
        lse_s = part.lse.reshape(B, C, H)
        with jax.named_scope("lse_merge"):
            o, _ = L.merge_partial_attention([o_u, o_s], [lse_u, lse_s])
        stats = part.stats
    else:
        with jax.named_scope("unique_attn"):
            o = L.flash_attention(q, k_ctx, v_ctx, causal=True,
                                  q_offset=start_pos + base,
                                  kv_offset=start_pos, kv_len=kv_valid,
                                  window=cfg.attn_window)
    x = x + lsc(_attn_out_proj(o, lp), "batch", "seq", None)
    h2 = L.rms_norm(x, lp["ln2"]["scale"], cfg.rms_eps)
    with jax.named_scope("mlp"):
        y, _ = _ffn(cfg, lp, h2)
    x = x + lsc(y, "batch", "seq", None)
    return x, kc, vc, stats


def prefill_chunk(cfg: ModelConfig, params: Params, tokens: jax.Array,
                  cache: KVCache, store: Optional[SharedKVStore] = None,
                  start_pos=0,
                  chunk_len: Optional[jax.Array] = None,
                  kernel: Optional[str] = None,
                  return_stats: bool = False):
    """Process one chunk of a long prompt; call repeatedly to prefill
    prompts past the largest bucket with a bounded jit cache.

    tokens: (B, C) the chunk, right-padded; ``chunk_len`` (traced scalar)
    is the number of real tokens in it. ``cache`` is the scratch context
    (L, B, V, KH·D) already holding ``cache.length`` earlier tokens.
    Returns (logits at the chunk's last real token, cache extended by
    ``chunk_len``), plus the per-layer ``DispatchStats`` with
    ``return_stats``. One compiled program per (C, V) shape pair regardless
    of prompt length; numerically equivalent to the single-shot prefill
    (allclose), not bitwise (different contraction shapes).
    """
    x = embed_inputs(cfg, params, tokens)
    B, C, _ = x.shape
    base = cache.length[0]
    if chunk_len is None:
        chunk_len = jnp.asarray(C, jnp.int32)
    chunk_len = jnp.asarray(chunk_len, jnp.int32)
    start = jnp.asarray(start_pos, jnp.int32)
    positions = start + base + jnp.arange(C)
    shared = _shared_xs(cfg, store)

    def scan_body(x, xs):
        lp, kc, vc, sh = xs if shared is not None else (*xs, None)
        x, kc, vc, st = _layer_prefill_chunk(cfg, x, lp, positions, kc, vc,
                                             base, chunk_len, sh, start,
                                             kernel=kernel)
        return x, (kc, vc, st)

    xs = ((params["layers"], cache.k, cache.v) if shared is None else
          (params["layers"], cache.k, cache.v, shared))
    x, (k_new, v_new, stats) = jax.lax.scan(scan_body, x, xs)
    x = L.rms_norm(x, params["final_norm"]["scale"], cfg.rms_eps)
    x_last = jax.lax.dynamic_index_in_dim(x, chunk_len - 1, axis=1,
                                          keepdims=False)
    logits = _lm_head(cfg, params, x_last)
    lengths = (cache.length + chunk_len).astype(jnp.int32)
    offsets = jnp.full_like(cache.offset, start)
    out = (logits, KVCache(k_new, v_new, lengths, offsets))
    return (*out, stats) if return_stats else out
