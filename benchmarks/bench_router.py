"""Router-quality ablation (supports the paper's 75%-sparsity assumption):
what fraction of true attention mass does the training-free mean-key
router's top-k capture, vs (a) oracle chunk ranking by actual attention
mass, (b) random chunk selection? Swept over k on a real (reduced) model's
corpus KV. The paper cites LongHeads/MoBA for ">=75% sparsity preserves
task performance"; this measures the mechanism on our stack.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs import get_config
from repro.core import build_store, route
from repro.kvcache import init_kv_cache
from repro.models import dense


def run(emit):
    cfg = dataclasses.replace(get_config("tinyllama-1.1b").reduced(),
                              dtype="float32")
    key = jax.random.PRNGKey(7)
    params = dense.init_params(cfg, key)
    E, C = 16, cfg.moska.chunk_size
    corpus = jax.random.randint(jax.random.fold_in(key, 1), (1, E * C), 0,
                                cfg.vocab_size)
    ccache = init_kv_cache(cfg.num_layers, 1, E * C, cfg.num_kv_heads,
                           cfg.head_dim, jnp.float32)
    _, ccache = dense.prefill(cfg, params, corpus, ccache)
    store = build_store(ccache.k[:, 0], ccache.v[:, 0], C,
                        head_dim=cfg.head_dim)

    # queries from a forward pass over fresh prompts (layer-0 q)
    B = 16
    toks = jax.random.randint(jax.random.fold_in(key, 2), (B, 8), 0,
                              cfg.vocab_size)
    x = params["embed"]["embed"][toks]
    from repro.models import layers as L
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    h = L.rms_norm(x, lp["ln1"]["scale"], cfg.rms_eps)
    q, _, _ = L.qkv_project(h, lp["attn"], cfg.num_heads, cfg.num_kv_heads,
                            cfg.head_dim)
    q = L.apply_rope(q, E * C + jnp.arange(8), cfg.rope_theta)[:, -1]

    # true attention mass per chunk (layer 0)
    KH, D = cfg.num_kv_heads, cfg.head_dim
    H = cfg.num_heads
    kf = store.k[0].transpose(0, 2, 1, 3).reshape(E * C, KH, D)
    qg = q.reshape(B, KH, H // KH, D)
    s = jnp.einsum("bkgd,skd->bkgs", qg, kf) / math.sqrt(D)
    p = jax.nn.softmax(s, axis=-1)
    mass = p.reshape(B, KH, H // KH, E, C).sum(-1).mean((1, 2))  # (B, E)

    # record per-k quality into the observability registry, then report
    # from its snapshot — same metric names a serving deployment would see
    reg = obs.get_registry()
    rng = np.random.default_rng(0)
    for k in (1, 2, 4, 8):
        with obs.span("bench.route", registry=reg, top_k=k):
            r = route(q, store.emb[0], k)
            jax.block_until_ready(r.chunk_ids)
        routed = np.asarray(jax.vmap(
            lambda m, ids: m[ids].sum())(mass, r.chunk_ids))
        oracle = np.sort(np.asarray(mass), axis=1)[:, -k:].sum(1)
        rand_ids = rng.integers(0, E, (B, k))
        rand = np.take_along_axis(np.asarray(mass), rand_ids, 1).sum(1)
        base = f"router/top{k}_of_{E}"
        reg.set_gauge(f"{base}/mass_captured", float(routed.mean()))
        reg.set_gauge(f"{base}/oracle_mass", float(oracle.mean()))
        reg.set_gauge(f"{base}/random_mass", float(rand.mean()))
        reg.set_gauge(f"{base}/recall_vs_oracle",
                      float((routed / np.maximum(oracle, 1e-9)).mean()))
    snap = reg.snapshot()
    for name, m in snap.items():
        if name.startswith("router/"):
            emit(name, 0.0, f"{m['value']:.3f}")
    lat = reg.get("span/bench.route/duration_s")
    if lat is not None and lat.count:
        emit(f"router/route_call_mean_us_B{B}", lat.mean * 1e6,
             f"n={lat.count}")
