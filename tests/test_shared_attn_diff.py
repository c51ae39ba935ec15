"""Differential harness for the shared-KV GEMM path.

Pins the Pallas kernel (interpret mode on CPU) against the two reference
implementations across ragged shapes:

  * ``shared_attention_batched(kernel='pallas')`` vs
    ``shared_attention_batched(kernel=None)`` (jnp math) vs
    ``shared_attention_gather_ref`` (per-request gather oracle)
  * raw ``kernels.shared_chunk_attn`` vs the jnp per-chunk reference with a
    kv-tile size that does NOT divide the chunk length (ragged tail tile)

Cases: chunk length not a multiple of ``block_c``, capacity overflow
(dropped queries), empty chunks (no queries routed), and single-query
groups. Output and LSE must agree to fp32 tolerance.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import router as router_lib
from repro.core import shared_attention as sa
from repro.core.router import Routing
from repro.kernels.shared_chunk_attn import shared_chunk_attention

KEY = jax.random.PRNGKey(0)
TOL = dict(rtol=3e-5, atol=3e-5)


def _kv(E, C, KH, D, key=KEY):
    """One layer's shared store, laid out per kv head: (E, KH, C, D)."""
    k = jax.random.normal(jax.random.fold_in(key, 1), (E, KH, C, D),
                          jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 2), (E, KH, C, D),
                          jnp.float32)
    return k, v


def _routing(chunk_ids, E):
    ids = jnp.asarray(chunk_ids, jnp.int32)
    G, K = ids.shape
    return Routing(ids, jnp.zeros((G, K), jnp.float32),
                   jnp.zeros((G, E), jnp.float32))


def _rand_routing(G, K, E, seed=0):
    # distinct chunks per group (routing semantics: top-k without repeats)
    keys = jax.random.split(jax.random.PRNGKey(seed), G)
    ids = jnp.stack([jax.random.permutation(k, E)[:K] for k in keys])
    return _routing(ids, E)


def _assert_partials_close(a, b, **tol):
    np.testing.assert_allclose(a.out, b.out, **(tol or TOL))
    np.testing.assert_allclose(a.lse, b.lse, **(tol or TOL))


# ---------------------------------------------------------------------------
# full path: pallas == jnp == gather oracle (no drops)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("G,Q,K,E,C,H,KH,D", [
    (6, 1, 3, 8, 16, 8, 2, 32),     # decode-shaped
    (4, 8, 2, 8, 16, 8, 2, 32),     # prefill blocks
    (1, 1, 1, 4, 8, 4, 4, 16),      # single-query group, MHA
    (1, 4, 8, 8, 8, 4, 1, 16),      # one group routed everywhere, MQA
    (5, 1, 2, 3, 24, 8, 2, 32),     # C=24: not 8/128-aligned
])
def test_pallas_vs_jnp_vs_gather(G, Q, K, E, C, H, KH, D):
    k, v = _kv(E, C, KH, D)
    r = _rand_routing(G, K, E, seed=G * 100 + K)
    q = jax.random.normal(jax.random.fold_in(KEY, 3), (G, Q, H, D),
                          jnp.float32)
    cap = G * K   # no capacity drops => all three must agree exactly
    ref = sa.shared_attention_gather_ref(q, k, v, r)
    jnp_p = sa.shared_attention_batched(q, k, v, r, capacity=cap)
    pal_p = sa.shared_attention_batched(q, k, v, r, capacity=cap,
                                        kernel="pallas")
    _assert_partials_close(jnp_p, ref)
    _assert_partials_close(pal_p, ref)
    _assert_partials_close(pal_p, jnp_p)


def test_ragged_chunk_vs_block_c_through_full_path():
    """block_c does not divide C: the kernel's tail-tile masking must keep
    the full path equal to the gather oracle."""
    G, Q, K, E, C, H, KH, D = 4, 1, 2, 4, 24, 8, 2, 32
    k, v = _kv(E, C, KH, D)
    r = _rand_routing(G, K, E, seed=7)
    q = jax.random.normal(jax.random.fold_in(KEY, 4), (G, Q, H, D),
                          jnp.float32)
    ref = sa.shared_attention_gather_ref(q, k, v, r)
    for block_c in (16, 10, 24, 7):
        pal = sa.shared_attention_batched(q, k, v, r, capacity=G * K,
                                          kernel="pallas", block_c=block_c)
        _assert_partials_close(pal, ref)


# ---------------------------------------------------------------------------
# raw kernel vs jnp per-chunk reference (direct dispatch control)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("E,cap,H,KH,D,C,block_c", [
    (4, 8, 8, 2, 32, 24, 16),       # ragged tail tile (24 = 16 + 8)
    (3, 8, 4, 4, 16, 17, 8),        # prime C, multiple ragged tiles
    (2, 16, 8, 1, 32, 32, 32),      # exact tiling, MQA
    (5, 8, 8, 2, 16, 5, 8),         # C < block_c (single clamped tile)
])
def test_kernel_vs_reference_ragged(E, cap, H, KH, D, C, block_c):
    key = jax.random.fold_in(KEY, E * 1000 + C)
    k, v = _kv(E, C, KH, D, key)
    qd = jax.random.normal(jax.random.fold_in(key, 3), (E, cap, H, D),
                           jnp.float32)
    # ragged validity incl. one fully-empty chunk (chunk 0: no queries)
    qmask = jax.random.bernoulli(jax.random.fold_in(key, 4), 0.6, (E, cap))
    qmask = qmask.at[0].set(False)
    out_k, lse_k = shared_chunk_attention(qd, k, v, qmask,
                                          block_c=block_c)
    out_r, lse_r = sa._chunk_batched_attention(qd[:, :, None], k, v, qmask)
    # masked slots: kernel zeroes the output, reference leaves it dangling
    # (both mark lse = -inf) — compare outputs on valid slots only
    valid = np.asarray(qmask)[:, :, None, None]
    np.testing.assert_allclose(np.where(valid, np.asarray(out_k), 0.0),
                               np.where(valid, np.asarray(out_r[:, :, 0]),
                                        0.0), **TOL)
    np.testing.assert_allclose(lse_k, lse_r[:, :, 0], **TOL)
    assert np.isfinite(np.asarray(out_k)).all()
    assert np.all(np.asarray(out_k)[~np.asarray(qmask)] == 0.0)
    # empty chunk: masked slots carry the -inf sentinel and zero output
    assert np.all(np.asarray(lse_k[0]) <= sa.NEG_INF / 2)
    assert np.all(np.asarray(out_k[0]) == 0.0)


# ---------------------------------------------------------------------------
# capacity overflow: drops must be identical across implementations
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("capacity", [1, 2, 8])
def test_capacity_overflow_pallas_equals_jnp(capacity):
    G, Q, K, E, C, H, KH, D = 8, 1, 2, 4, 16, 8, 2, 32
    k, v = _kv(E, C, KH, D)
    r = _rand_routing(G, K, E, seed=3)
    q = jax.random.normal(jax.random.fold_in(KEY, 5), (G, Q, H, D),
                          jnp.float32)
    jnp_p = sa.shared_attention_batched(q, k, v, r, capacity=capacity)
    pal_p = sa.shared_attention_batched(q, k, v, r, capacity=capacity,
                                        kernel="pallas")
    _assert_partials_close(pal_p, jnp_p)
    assert np.isfinite(np.asarray(pal_p.out)).all()
    # with G*K = 16 routes into E*capacity slots, overflow must drop:
    # groups whose every route dropped carry the -inf LSE sentinel
    if capacity * E < G * K:
        flat, pos, keep = router_lib.dispatch_plan(r.chunk_ids, E, capacity)
        keep = np.asarray(keep).reshape(G, K)
        lse = np.asarray(pal_p.lse)
        for g in range(G):
            if not keep[g].any():
                assert np.all(lse[g] <= sa.NEG_INF / 2)
            else:
                assert np.isfinite(lse[g]).all()


def test_empty_chunks_full_path():
    """All groups route to a single chunk; the other chunks run empty
    through the kernel and must not perturb the result."""
    G, Q, E, C, H, KH, D = 5, 1, 6, 8, 8, 2, 16
    k, v = _kv(E, C, KH, D)
    r = _routing(np.zeros((G, 1), np.int32), E)
    q = jax.random.normal(jax.random.fold_in(KEY, 6), (G, Q, H, D),
                          jnp.float32)
    ref = sa.shared_attention_gather_ref(q, k, v, r)
    for kern in (None, "pallas"):
        got = sa.shared_attention_batched(q, k, v, r, capacity=G,
                                          kernel=kern)
        _assert_partials_close(got, ref)
