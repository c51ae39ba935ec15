"""Per-kernel validation: shape/dtype sweeps, interpret-mode kernel vs the
pure-jnp oracle in repro.kernels.ref (assignment requirement (c))."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref as kref
from repro.kernels.decode_attn import decode_attention
from repro.kernels.lse_merge import lse_merge
from repro.kernels.paged_decode_attn import paged_decode_attention
from repro.kernels.router_score import router_scores
from repro.kernels.shared_chunk_attn import shared_chunk_attention
from repro.models import layers as Lyr

KEY = jax.random.PRNGKey(0)


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


def _tols(dtype):
    return dict(rtol=2e-5, atol=2e-5) if dtype == jnp.float32 else \
        dict(rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("E,cap,H,KH,D,C,blk", [
    (3, 8, 4, 2, 32, 64, 16),
    (2, 16, 8, 8, 64, 128, 128),
    (1, 4, 2, 1, 16, 32, 32),
    (4, 8, 6, 2, 64, 48, 16),     # ragged C vs blk
    (2, 8, 4, 4, 128, 256, 512),  # blk > C
])
def test_shared_chunk_attention(dtype, E, cap, H, KH, D, C, blk):
    qd = _rand(jax.random.fold_in(KEY, 1), (E, cap, H, D), dtype)
    k = _rand(jax.random.fold_in(KEY, 2), (E, KH, C, D), dtype)
    v = _rand(jax.random.fold_in(KEY, 3), (E, KH, C, D), dtype)
    qm = jax.random.bernoulli(jax.random.fold_in(KEY, 4), 0.7, (E, cap))
    o1, l1 = shared_chunk_attention(qd, k, v, qm, block_c=blk)
    o2, l2 = kref.shared_chunk_attention_ref(qd, k, v, qm)
    np.testing.assert_allclose(np.float32(o1), np.float32(o2),
                               **_tols(dtype))
    np.testing.assert_allclose(l1, l2, rtol=2e-2 if dtype == jnp.bfloat16
                               else 2e-5, atol=2e-2)
    # masked slots must carry -inf lse and zero output
    assert np.all(np.asarray(l1)[~np.asarray(qm)] < -1e29)
    assert np.all(np.float32(o1)[~np.asarray(qm)] == 0.0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("L,B,H,KH,D,S,blk,window", [
    (2, 4, 8, 8, 32, 100, 32, 0),      # MHA, S ragged vs blk
    (3, 3, 8, 2, 32, 64, 16, 0),       # GQA G=4, KH·D = 64 < 128
    (2, 2, 16, 2, 64, 256, 128, 0),    # GQA G=8
    (1, 3, 2, 1, 16, 33, 16, 0),       # one kv head of 16 lanes
    (2, 4, 16, 4, 128, 512, 128, 48),  # sliding window
    (2, 2, 16, 16, 64, 768, 256, 0),   # qwen1.5-0.5b's heads and max_seq
])
def test_decode_attention(dtype, L, B, H, KH, D, S, blk, window):
    """The kernel reads layer L-1 of a stacked lane-dense (L, B, S, KH·D)
    cache in place; it matches ``layers.decode_attention`` on that layer
    split into heads, for lengths from 1 to S."""
    q = _rand(jax.random.fold_in(KEY, 1), (B, H, D), dtype)
    k = _rand(jax.random.fold_in(KEY, 2), (L, B, S, KH * D), dtype)
    v = _rand(jax.random.fold_in(KEY, 3), (L, B, S, KH * D), dtype)
    lens = jax.random.randint(jax.random.fold_in(KEY, 4), (B,), 1, S + 1)
    lens = lens.at[0].set(1).at[-1].set(S)
    o1, l1 = decode_attention(q, k, v, lens, L - 1, window=window,
                              block_s=blk)
    o2, l2 = Lyr.decode_attention(q, k[L - 1].reshape(B, S, KH, D),
                                  v[L - 1].reshape(B, S, KH, D), lens,
                                  window=window, return_lse=True)
    np.testing.assert_allclose(np.float32(o1), np.float32(o2),
                               **_tols(dtype))
    np.testing.assert_allclose(l1, l2, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,KH,D,N,bs,M", [
    (3, 8, 2, 32, 16, 16, 4),
    (2, 4, 4, 64, 9, 32, 3),
    (1, 16, 8, 128, 32, 8, 8),
])
def test_paged_decode_attention(dtype, B, H, KH, D, N, bs, M):
    """Scalar-prefetch paged kernel vs the gather oracle, and the oracle
    vs the dense reference on an equivalently-filled contiguous cache
    (bitwise — the engine's paged/slotted bit-identity rests on it)."""
    from repro.kernels.paged_decode_attn import paged_decode_attention_ref
    q = _rand(jax.random.fold_in(KEY, 11), (B, H, D), dtype)
    k_pool = _rand(jax.random.fold_in(KEY, 12), (N, bs, KH, D), dtype)
    v_pool = _rand(jax.random.fold_in(KEY, 13), (N, bs, KH, D), dtype)
    # distinct non-null pages per slot, scrambled order
    perm = jax.random.permutation(jax.random.fold_in(KEY, 14),
                                  jnp.arange(1, N))[:B * M]
    table = perm.reshape(B, M).astype(jnp.int32)
    lens = jax.random.randint(jax.random.fold_in(KEY, 15), (B,), 1,
                              M * bs + 1)
    o1, l1 = paged_decode_attention(q, k_pool, v_pool, table, lens)
    o2, l2 = paged_decode_attention_ref(q, k_pool, v_pool, table, lens)
    np.testing.assert_allclose(np.float32(o1), np.float32(o2),
                               **_tols(dtype))
    np.testing.assert_allclose(l1, l2, rtol=2e-2, atol=2e-2)
    # oracle == dense ref, bit for bit, on the gathered contiguous cache
    from repro.kvcache.paged import gather_layer
    kc = gather_layer(k_pool, table)
    vc = gather_layer(v_pool, table)
    o3, l3 = kref.decode_attention_ref(q, kc, vc, lens)
    np.testing.assert_array_equal(np.asarray(o2), np.asarray(o3))
    np.testing.assert_array_equal(np.asarray(l2), np.asarray(l3))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("P,N,H,D,blk", [
    (2, 64, 4, 32, 16), (3, 7, 2, 16, 8), (4, 128, 8, 64, 128),
])
def test_lse_merge(dtype, P, N, H, D, blk):
    outs = _rand(jax.random.fold_in(KEY, 5), (P, N, H, D), dtype)
    lses = jax.random.normal(jax.random.fold_in(KEY, 6), (P, N, H)) * 3
    o1, l1 = lse_merge(outs, lses, block_n=blk)
    o2, l2 = kref.lse_merge_ref(outs, lses)
    np.testing.assert_allclose(np.float32(o1), np.float32(o2),
                               **_tols(dtype))
    np.testing.assert_allclose(l1, l2, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("G,H,KH,D,E,bg,be", [
    (8, 8, 2, 32, 16, 4, 4),
    (5, 4, 4, 16, 7, 8, 8),
    (128, 8, 8, 64, 512, 128, 512),
])
def test_router_scores(G, H, KH, D, E, bg, be):
    q = jax.random.normal(jax.random.fold_in(KEY, 7), (G, H, D))
    emb = jax.random.normal(jax.random.fold_in(KEY, 8), (E, KH, D))
    s1 = router_scores(q, emb, block_g=bg, block_e=be)
    s2 = kref.router_scores_ref(q, emb)
    np.testing.assert_allclose(s1, s2, rtol=2e-5, atol=2e-5)


def test_merge_of_decode_splits_equals_joint():
    """Flash-decoding invariant: decode over split caches + lse_merge ==
    decode over the whole cache (the disaggregated combine is exact)."""
    B, H, KH, D, S = 3, 8, 2, 32, 128
    q = _rand(jax.random.fold_in(KEY, 1), (B, H, D), jnp.float32)
    k = _rand(jax.random.fold_in(KEY, 2), (1, B, S, KH * D), jnp.float32)
    v = _rand(jax.random.fold_in(KEY, 3), (1, B, S, KH * D), jnp.float32)
    full = jnp.full((B,), S, jnp.int32)
    oj, _ = decode_attention(q, k, v, full, 0)
    half = jnp.full((B,), S // 2, jnp.int32)
    o1, l1 = decode_attention(q, k[:, :, :S // 2], v[:, :, :S // 2], half, 0)
    o2, l2 = decode_attention(q, k[:, :, S // 2:], v[:, :, S // 2:], half, 0)
    om, _ = lse_merge(jnp.stack([o1, o2]), jnp.stack([l1, l2]))
    np.testing.assert_allclose(np.float32(om), np.float32(oj),
                               rtol=2e-5, atol=2e-5)


def test_int8_store_end_to_end():
    """Dense decode with a quantized store ~= decode with the fp store."""
    import dataclasses
    from repro.configs import get_config
    from repro.core.shared_kv import build_store
    from repro.kvcache import init_kv_cache
    from repro.models import dense
    cfg = dataclasses.replace(get_config("tinyllama-1.1b").reduced(),
                              dtype="float32")
    params = dense.init_params(cfg, KEY)
    B, CL = 2, 128
    ctoks = jax.random.randint(jax.random.fold_in(KEY, 5), (1, CL), 0,
                               cfg.vocab_size)
    ccache = init_kv_cache(cfg.num_layers, 1, CL, cfg.num_kv_heads,
                           cfg.head_dim, jnp.float32)
    _, ccache = dense.prefill(cfg, params, ctoks, ccache)
    s_fp = build_store(ccache.k[:, 0], ccache.v[:, 0], cfg.moska.chunk_size,
                       head_dim=cfg.head_dim)
    s_q8 = build_store(ccache.k[:, 0], ccache.v[:, 0], cfg.moska.chunk_size,
                       quantize=True, head_dim=cfg.head_dim)
    assert s_q8.quantized and s_q8.k.dtype == jnp.int8
    toks = jax.random.randint(jax.random.fold_in(KEY, 6), (B, 8), 0,
                              cfg.vocab_size)
    c1 = init_kv_cache(cfg.num_layers, B, 12, cfg.num_kv_heads,
                       cfg.head_dim, jnp.float32)
    _, c1 = dense.prefill(cfg, params, toks, c1, store=s_fp, start_pos=CL)
    l_fp, _ = dense.decode_step(cfg, params, toks[:, -1], c1, store=s_fp)
    l_q8, _ = dense.decode_step(cfg, params, toks[:, -1], c1, store=s_q8)
    np.testing.assert_allclose(np.asarray(l_fp), np.asarray(l_q8),
                               rtol=0.1, atol=0.1)
