"""Long-context decode via MoSKA routing (the long_500k mechanism at
reduced scale): a context far larger than what full attention would read
per step is registered as shared chunks; each decode step reads only the
routed top-k — sub-quadratic in context length — and the output provably
matches full attention when routing is exhaustive.

Also demonstrates the Pallas kernel path (interpret mode on CPU).

    PYTHONPATH=src python examples/long_context_decode.py
"""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import build_store
from repro.kvcache import init_kv_cache
from repro.models import dense

cfg = dataclasses.replace(get_config("llama3-8b").reduced(),
                          dtype="float32")
key = jax.random.PRNGKey(0)
params = dense.init_params(cfg, key)

# a "long" context: 16 chunks; decode reads top-2 => 8x fewer tokens/step
ctx_len = 16 * cfg.moska.chunk_size
ctx = jax.random.randint(jax.random.fold_in(key, 1), (1, ctx_len), 0,
                         cfg.vocab_size)
ccache = init_kv_cache(cfg.num_layers, 1, ctx_len, cfg.num_kv_heads,
                       cfg.head_dim, jnp.float32)
_, ccache = dense.prefill(cfg, params, ctx, ccache)
store = build_store(ccache.k[:, 0], ccache.v[:, 0], cfg.moska.chunk_size,
                    head_dim=cfg.head_dim)
print(f"context: {ctx_len} tokens as {store.num_chunks} chunks; "
      f"router reads top-{cfg.moska.top_k_chunks} per step "
      f"({100 * cfg.moska.top_k_chunks / store.num_chunks:.0f}% of context)")

B = 2
prompt = jax.random.randint(jax.random.fold_in(key, 2), (B, 8), 0,
                            cfg.vocab_size)
cache = init_kv_cache(cfg.num_layers, B, 64, cfg.num_kv_heads,
                      cfg.head_dim, jnp.float32)
logits, cache = dense.prefill(cfg, params, prompt, cache, store=store,
                              start_pos=ctx_len)
tok = jnp.argmax(logits, -1).astype(jnp.int32)

decode = jax.jit(lambda t, c: dense.decode_step(cfg, params, t, c,
                                                store=store))
toks = []
t0 = time.perf_counter()
for _ in range(8):
    logits, cache = decode(tok, cache)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    toks.append(np.asarray(tok))
print(f"decoded 8 tokens x {B} requests in "
      f"{time.perf_counter() - t0:.1f}s: {np.stack(toks)[:, 0]}")

# kernel-path parity (Pallas interpret mode)
l_jnp, _ = dense.decode_step(cfg, params, tok, cache, store=store)
l_pal, _ = dense.decode_step(cfg, params, tok, cache, store=store,
                             kernel="pallas")
print(f"pallas-vs-jnp decode max|diff| = "
      f"{float(jnp.max(jnp.abs(l_jnp - l_pal))):.2e}")
assert float(jnp.max(jnp.abs(l_jnp - l_pal))) < 1e-3
print("OK")
