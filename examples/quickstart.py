"""Quickstart: the MoSKA mechanism in ~60 lines.

Builds a small dense model, precomputes a shared corpus' KV chunks,
and shows that routed Shared-KV-Attention decode (a) matches monolithic
attention under full routing, and (b) reads only top-k chunks when sparse.

    PYTHONPATH=src python examples/quickstart.py
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import build_store
from repro.kvcache import init_kv_cache
from repro.models import dense

cfg = dataclasses.replace(get_config("tinyllama-1.1b").reduced(),
                          dtype="float32")
key = jax.random.PRNGKey(0)
params = dense.init_params(cfg, key)
print(f"model: {cfg.name} ({cfg.num_layers}L d={cfg.d_model})")

# --- 1. precompute the shared corpus KV once (the persistent asset) ------
corpus_len = 256
corpus = jax.random.randint(jax.random.fold_in(key, 1), (1, corpus_len),
                            0, cfg.vocab_size)
ccache = init_kv_cache(cfg.num_layers, 1, corpus_len, cfg.num_kv_heads,
                       cfg.head_dim, jnp.float32)
_, ccache = dense.prefill(cfg, params, corpus, ccache)
store = build_store(ccache.k[:, 0], ccache.v[:, 0], cfg.moska.chunk_size,
                    head_dim=cfg.head_dim)
print(f"shared store: {store.num_chunks} chunks x {store.chunk_size} tokens")

# --- 2. concurrent requests decode against the shared store --------------
B, S = 4, 12
prompts = jax.random.randint(jax.random.fold_in(key, 2), (B, S), 0,
                             cfg.vocab_size)
cache = init_kv_cache(cfg.num_layers, B, S + 8, cfg.num_kv_heads,
                      cfg.head_dim, jnp.float32)
logits, cache = dense.prefill(cfg, params, prompts, cache, store=store,
                              start_pos=corpus_len)
nxt = jnp.argmax(logits, -1).astype(jnp.int32)
logits, cache = dense.decode_step(cfg, params, nxt, cache, store=store)
print("sparse routed decode logits[0,:4] =", np.asarray(logits)[0, :4])

# --- 3. exactness: full routing == monolithic context ---------------------
full = dataclasses.replace(cfg, moska=dataclasses.replace(
    cfg.moska, top_k_chunks=store.num_chunks))
cache2 = init_kv_cache(cfg.num_layers, B, S + 8, cfg.num_kv_heads,
                       cfg.head_dim, jnp.float32)
lg, cache2 = dense.prefill(full, params, prompts, cache2, store=store,
                           start_pos=corpus_len)
nxt2 = jnp.argmax(lg, -1).astype(jnp.int32)
lg, _ = dense.decode_step(full, params, nxt2, cache2, store=store)

mono = jnp.concatenate([jnp.tile(corpus, (B, 1)), prompts,
                        nxt2[:, None]], axis=1)
cache3 = init_kv_cache(cfg.num_layers, B, mono.shape[1] + 4,
                       cfg.num_kv_heads, cfg.head_dim, jnp.float32)
lm, _ = dense.prefill(cfg, params, mono, cache3)
err = float(jnp.max(jnp.abs(lg - lm)))
print(f"full-routing decode vs monolithic-context decode: max|diff|={err:.2e}")
assert err < 1e-3
print("OK — Shared KV Attention is exact under full routing.")
