"""MoSKA core invariants: routing, dispatch, batched-vs-gather equivalence,
exact LSE merging, end-to-end exactness under full routing, and
property tests on the system's invariants.

``hypothesis`` is optional: when installed (see requirements-dev.txt) the
randomized property tests run; without it they skip and the deterministic
fallback cases below keep the same invariants covered.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # pragma: no cover - exercised on lean installs
    HAVE_HYPOTHESIS = False

needs_hypothesis = pytest.mark.skipif(
    not HAVE_HYPOTHESIS, reason="hypothesis not installed "
    "(pip install -r requirements-dev.txt)")

from repro.configs import get_config
from repro.configs.base import MoSKAConfig
from repro.core import (MoskaLayerContext, Routing, build_store,
                        moska_decode_attention, route,
                        shared_attention_batched,
                        shared_attention_gather_ref)
from repro.core import router as router_lib
from repro.kvcache import init_kv_cache
from repro.models import dense
from repro.models import layers as L

KEY = jax.random.PRNGKey(0)


def _store(E=8, C=16, KH=2, D=32, layers=1, key=KEY):
    k = jax.random.normal(jax.random.fold_in(key, 1), (layers, E * C, KH, D))
    v = jax.random.normal(jax.random.fold_in(key, 2), (layers, E * C, KH, D))
    return build_store(k, v, C)


def _tokens_major(x):
    """One layer's store (E, KH, C, D) as corpus tokens (E*C, KH, D)."""
    E, KH, C, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(E * C, KH, D)


# ---------------------------------------------------------------------------
# routing & dispatch
# ---------------------------------------------------------------------------

def test_route_topk_sound():
    store = _store()
    q = jax.random.normal(jax.random.fold_in(KEY, 3), (6, 8, 32))
    r = route(q, store.emb[0], 3)
    assert r.chunk_ids.shape == (6, 3)
    # selected scores are the k largest of the full score row
    full = np.asarray(r.full_scores)
    for g in range(6):
        top = np.sort(full[g])[-3:][::-1]
        np.testing.assert_allclose(np.asarray(r.scores[g]), top, rtol=1e-6)


def _check_dispatch_plan_invariants(G, K, E, seed):
    """Dispatch positions are unique per chunk, in-capacity slots keep
    every (group, k) pair, and counts never exceed capacity."""
    K = min(K, E)
    ids = jax.random.randint(jax.random.PRNGKey(seed), (G, K), 0, E)
    cap = max(1, (G * K) // E)
    flat, pos, keep = router_lib.dispatch_plan(ids, E, cap)
    flat, pos, keep = map(np.asarray, (flat, pos, keep))
    # kept slots have unique (chunk, pos) and pos < capacity
    kept = [(c, p) for c, p, k in zip(flat, pos, keep) if k]
    assert len(set(kept)) == len(kept)
    assert all(p < cap for _, p in kept)
    # per-chunk kept count == min(capacity, total routed there)
    for e in range(E):
        total = int((flat == e).sum())
        kept_e = int(((flat == e) & keep).sum())
        assert kept_e == min(cap, total)


@pytest.mark.parametrize("G,K,E,seed", [
    (1, 1, 1, 0), (12, 4, 8, 1), (5, 3, 4, 7), (9, 2, 3, 11),
    (12, 1, 8, 2), (2, 4, 5, 13),
])
def test_dispatch_plan_invariants_cases(G, K, E, seed):
    """Deterministic fallback cases (always run, hypothesis or not)."""
    _check_dispatch_plan_invariants(G, K, E, seed)


if HAVE_HYPOTHESIS:
    @needs_hypothesis
    @given(st.integers(1, 12), st.integers(1, 4), st.integers(1, 8),
           st.integers(0, 2**31 - 1))
    @settings(max_examples=30, deadline=None)
    def test_dispatch_plan_invariants(G, K, E, seed):
        _check_dispatch_plan_invariants(G, K, E, seed)


def test_required_capacity_mxu_aligned():
    cap = router_lib.required_capacity(256, 8, 64, 2.0)
    assert cap % 8 == 0 and cap >= 256 * 8 / 64


# ---------------------------------------------------------------------------
# batched == gather == dense
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("G,Q,K", [(6, 1, 3), (4, 8, 2), (1, 4, 8)])
def test_batched_equals_gather(G, Q, K):
    store = _store()
    E = store.num_chunks
    K = min(K, E)
    q = jax.random.normal(jax.random.fold_in(KEY, 4), (G, Q, 8, 32))
    r = route(jnp.mean(q, axis=1), store.emb[0], K)
    b = shared_attention_batched(q, store.k[0], store.v[0], r,
                                 capacity=G * K)
    g = shared_attention_gather_ref(q, store.k[0], store.v[0], r)
    np.testing.assert_allclose(b.out, g.out, rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(b.lse, g.lse, rtol=3e-5, atol=3e-5)


def test_full_routing_equals_dense_attention():
    store = _store(E=4, C=8)
    q = jax.random.normal(jax.random.fold_in(KEY, 5), (5, 1, 8, 32))
    r = route(q[:, 0], store.emb[0], store.num_chunks)
    b = shared_attention_batched(q, store.k[0], store.v[0], r,
                                 capacity=5 * store.num_chunks)
    kf = _tokens_major(store.k[0])
    vf = _tokens_major(store.v[0])
    qg = q.reshape(5, 1, 2, 4, 32)
    s = jnp.einsum("gqkhd,skd->gqkhs", qg, kf) / math.sqrt(32)
    p = jax.nn.softmax(s, -1)
    o = jnp.einsum("gqkhs,skd->gqkhd", p, vf).reshape(5, 1, 8, 32)
    np.testing.assert_allclose(b.out, o, rtol=3e-5, atol=3e-5)


def test_capacity_drops_degrade_gracefully():
    """With capacity 1 per chunk, outputs stay finite and LSE marks drops."""
    store = _store()
    q = jax.random.normal(jax.random.fold_in(KEY, 6), (8, 1, 8, 32))
    r = route(q[:, 0], store.emb[0], 2)
    b = shared_attention_batched(q, store.k[0], store.v[0], r, capacity=1)
    assert np.isfinite(np.asarray(b.out)).all()


def _check_merge_exactness(G, K, seed):
    """Unique ⊕ shared LSE merge == softmax over the union of key sets."""
    key = jax.random.PRNGKey(seed)
    E, C, KH, D, H, S = 4, 8, 2, 16, 4, 12
    store = _store(E=E, C=C, KH=KH, D=D, key=key)
    K = min(K, E)
    q = jax.random.normal(jax.random.fold_in(key, 3), (G, H, D))
    kc = jax.random.normal(jax.random.fold_in(key, 4), (G, S, KH, D))
    vc = jax.random.normal(jax.random.fold_in(key, 5), (G, S, KH, D))
    lens = jax.random.randint(jax.random.fold_in(key, 6), (G,), 1, S + 1)
    r = route(q, store.emb[0], E)   # full routing => exact
    ctx = MoskaLayerContext(store.k[0], store.v[0], r)
    # the unique path reads a stacked lane-dense cache (L, B, S, KH·D)
    out, _ = moska_decode_attention(q, kc.reshape(1, G, S, KH * D),
                                    vc.reshape(1, G, S, KH * D), lens, ctx,
                                    MoSKAConfig(top_k_chunks=E))
    for g in range(G):
        keys = jnp.concatenate([_tokens_major(store.k[0]),
                                kc[g, :lens[g]]], 0)
        vals = jnp.concatenate([_tokens_major(store.v[0]),
                                vc[g, :lens[g]]], 0)
        qg = q[g].reshape(KH, H // KH, D)
        s = jnp.einsum("khd,skd->khs", qg, keys) / math.sqrt(D)
        p = jax.nn.softmax(s, -1)
        o = jnp.einsum("khs,skd->khd", p, vals).reshape(H, D)
        np.testing.assert_allclose(out[g], o, rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("G,K,seed", [(2, 1, 0), (6, 3, 1), (4, 2, 42)])
def test_merge_exactness_cases(G, K, seed):
    """Deterministic fallback cases (always run, hypothesis or not)."""
    _check_merge_exactness(G, K, seed)


if HAVE_HYPOTHESIS:
    @needs_hypothesis
    @given(st.integers(2, 6), st.integers(1, 3), st.integers(0, 2**31 - 1))
    @settings(max_examples=15, deadline=None)
    def test_property_merge_exactness(G, K, seed):
        _check_merge_exactness(G, K, seed)


# ---------------------------------------------------------------------------
# end-to-end: model + store
# ---------------------------------------------------------------------------

def test_build_store_from_lane_dense_prefill():
    """The store built from a prefill's lane-dense (L, N, KH·D) cache
    equals the one built from the same KV split into (L, N, KH, D), with
    or without int8; without ``head_dim`` the slab is refused."""
    cfg = dataclasses.replace(get_config("tinyllama-1.1b").reduced(),
                              dtype="float32")
    params = dense.init_params(cfg, jax.random.PRNGKey(0))
    N, KH, D = 128, cfg.num_kv_heads, cfg.head_dim
    toks = jax.random.randint(jax.random.fold_in(KEY, 9), (1, N), 0,
                              cfg.vocab_size)
    cache = init_kv_cache(cfg.num_layers, 1, N, KH, D, jnp.float32)
    _, cache = dense.prefill(cfg, params, toks, cache)
    k, v = cache.k[:, 0], cache.v[:, 0]
    assert k.shape == (cfg.num_layers, N, KH * D)
    C = cfg.moska.chunk_size
    for quantize in (False, True):
        got = build_store(k, v, C, quantize=quantize, head_dim=D)
        want = build_store(k.reshape(-1, N, KH, D), v.reshape(-1, N, KH, D),
                           C, quantize=quantize)
        for a, b in zip(got, want):
            if a is not None:
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError, match="head_dim"):
        build_store(k, v, C)


def test_moska_decode_equals_monolithic_context():
    cfg = dataclasses.replace(get_config("tinyllama-1.1b").reduced(),
                              dtype="float32")
    params = dense.init_params(cfg, jax.random.PRNGKey(0))
    B, S, CL = 2, 17, 128
    toks = jax.random.randint(jax.random.fold_in(KEY, 1), (B, S), 0,
                              cfg.vocab_size)
    ctoks = jax.random.randint(jax.random.fold_in(KEY, 2), (1, CL), 0,
                               cfg.vocab_size)
    ccache = init_kv_cache(cfg.num_layers, 1, CL, cfg.num_kv_heads,
                           cfg.head_dim, jnp.float32)
    _, ccache = dense.prefill(cfg, params, ctoks, ccache)
    store = build_store(ccache.k[:, 0], ccache.v[:, 0],
                        cfg.moska.chunk_size, head_dim=cfg.head_dim)
    cfgf = dataclasses.replace(cfg, moska=dataclasses.replace(
        cfg.moska, top_k_chunks=store.num_chunks))
    cache = init_kv_cache(cfg.num_layers, B, S + 4, cfg.num_kv_heads,
                          cfg.head_dim, jnp.float32)
    _, cache = dense.prefill(cfgf, params, toks[:, :S - 1], cache,
                             store=store, start_pos=CL)
    ld, _ = dense.decode_step(cfgf, params, toks[:, S - 1], cache,
                              store=store)
    full = jnp.concatenate([jnp.tile(ctoks, (B, 1)), toks], 1)
    cache2 = init_kv_cache(cfg.num_layers, B, CL + S + 4, cfg.num_kv_heads,
                           cfg.head_dim, jnp.float32)
    lf, _ = dense.prefill(cfg, params, full, cache2)
    np.testing.assert_allclose(ld, lf, rtol=2e-4, atol=2e-4)


def test_sparse_routing_approximates_dense():
    """top-1 of 2 chunks: finite, and closer to exact than random logits."""
    cfg = dataclasses.replace(get_config("tinyllama-1.1b").reduced(),
                              dtype="float32")
    params = dense.init_params(cfg, jax.random.PRNGKey(0))
    B, S, CL = 2, 9, 128
    toks = jax.random.randint(jax.random.fold_in(KEY, 3), (B, S), 0,
                              cfg.vocab_size)
    ctoks = jax.random.randint(jax.random.fold_in(KEY, 4), (1, CL), 0,
                               cfg.vocab_size)
    ccache = init_kv_cache(cfg.num_layers, 1, CL, cfg.num_kv_heads,
                           cfg.head_dim, jnp.float32)
    _, ccache = dense.prefill(cfg, params, ctoks, ccache)
    store = build_store(ccache.k[:, 0], ccache.v[:, 0],
                        cfg.moska.chunk_size, head_dim=cfg.head_dim)
    sparse = dataclasses.replace(cfg, moska=dataclasses.replace(
        cfg.moska, top_k_chunks=1))
    cache = init_kv_cache(cfg.num_layers, B, S + 4, cfg.num_kv_heads,
                          cfg.head_dim, jnp.float32)
    _, cache = dense.prefill(sparse, params, toks[:, :S - 1], cache,
                             store=store, start_pos=CL)
    ld, _ = dense.decode_step(sparse, params, toks[:, S - 1], cache,
                              store=store)
    assert np.isfinite(np.asarray(ld)).all()


def test_pallas_kernel_path_matches_jnp_path():
    """decode with kernel='pallas' must equal the jnp shared path."""
    cfg = dataclasses.replace(get_config("tinyllama-1.1b").reduced(),
                              dtype="float32")
    params = dense.init_params(cfg, jax.random.PRNGKey(0))
    B, CL = 2, 128
    ctoks = jax.random.randint(jax.random.fold_in(KEY, 5), (1, CL), 0,
                               cfg.vocab_size)
    ccache = init_kv_cache(cfg.num_layers, 1, CL, cfg.num_kv_heads,
                           cfg.head_dim, jnp.float32)
    _, ccache = dense.prefill(cfg, params, ctoks, ccache)
    store = build_store(ccache.k[:, 0], ccache.v[:, 0],
                        cfg.moska.chunk_size, head_dim=cfg.head_dim)
    cache = init_kv_cache(cfg.num_layers, B, 8, cfg.num_kv_heads,
                          cfg.head_dim, jnp.float32)
    toks = jax.random.randint(jax.random.fold_in(KEY, 6), (B, 4), 0,
                              cfg.vocab_size)
    _, cache = dense.prefill(cfg, params, toks, cache, store=store,
                             start_pos=CL)
    l1, _ = dense.decode_step(cfg, params, toks[:, -1], cache, store=store)
    l2, _ = dense.decode_step(cfg, params, toks[:, -1], cache, store=store,
                              kernel="pallas")
    np.testing.assert_allclose(l1, l2, rtol=2e-4, atol=2e-4)


def test_pallas_kernel_prefill_matches_jnp_path():
    """prefill (bucket-padded) and chunked prefill with kernel='pallas'
    must equal the jnp shared path."""
    cfg = dataclasses.replace(get_config("tinyllama-1.1b").reduced(),
                              dtype="float32")
    params = dense.init_params(cfg, jax.random.PRNGKey(0))
    CL, S = 128, 16
    ctoks = jax.random.randint(jax.random.fold_in(KEY, 7), (1, CL), 0,
                               cfg.vocab_size)
    ccache = init_kv_cache(cfg.num_layers, 1, CL, cfg.num_kv_heads,
                           cfg.head_dim, jnp.float32)
    _, ccache = dense.prefill(cfg, params, ctoks, ccache)
    store = build_store(ccache.k[:, 0], ccache.v[:, 0],
                        cfg.moska.chunk_size, head_dim=cfg.head_dim)
    toks = jax.random.randint(jax.random.fold_in(KEY, 8), (1, S), 0,
                              cfg.vocab_size)
    outs = {}
    for kern in (None, "pallas"):
        cache = init_kv_cache(cfg.num_layers, 1, S, cfg.num_kv_heads,
                              cfg.head_dim, jnp.float32)
        lp, cp = dense.prefill(cfg, params, toks, cache, store=store,
                               start_pos=CL, true_len=jnp.int32(S - 3),
                               kernel=kern)
        ctx = init_kv_cache(cfg.num_layers, 1, S, cfg.num_kv_heads,
                            cfg.head_dim, jnp.float32)
        lc, cc = dense.prefill_chunk(cfg, params, toks, ctx, store=store,
                                     start_pos=CL, kernel=kern)
        outs[kern] = (lp, cp.k, lc, cc.k)
    for a, b in zip(outs[None], outs["pallas"]):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)
