"""Serving hot-path tests: donated persistent cache, bucketed prefill,
per-slot cache writes, mixed-corpus wave isolation, livelock detection.

Differential guarantees:
  * donation + persistent cache produce bit-identical generations to the
    copying (donate_cache=False) path — donation only aliases buffers
  * bucketed prefill (pad + masked routing + dynamic logit index) produces
    the same generations as exact-length prefill
  * a prompt-length sweep compiles at most one prefill program per bucket
  * per-slot writes never leak stale KV across slot reuse (dtypes, offsets)
  * the decode append writes what a per-slot dynamic_update_slice writes,
    clamped lengths included
  * corpus-B requests in a mixed-corpus stream decode against store B
    (regression: the scheduler used to mix corpora into one wave and the
    engine fed every slot the resident store)
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_config
from repro.data.pipeline import CorpusSpec, synthesize_corpus
from repro.kvcache.cache import (KVCache, append_token, init_kv_cache,
                                 read_slot, write_slot_prefix)
from repro.models.model import build_model
from repro.serving.engine import (EngineConfig, ServingEngine, bucket_for,
                                  resolve_prefill_buckets)

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("tinyllama-1.1b").reduced()
    model = build_model(cfg)
    params = model.init(KEY)
    return cfg, params


def _fresh_registry():
    reg = obs.MetricsRegistry()
    return reg, obs.set_registry(reg)


def _run(cfg, params, ecfg, requests, corpora=()):
    """Run one engine on a fresh registry; returns (finished, registry)."""
    reg, prev = _fresh_registry()
    try:
        eng = ServingEngine(cfg, params, ecfg)
        for cid, toks in corpora:
            eng.register_corpus(cid, toks)
        for prompt, new, cid in requests:
            eng.submit(prompt, max_new_tokens=new, corpus_id=cid)
        done = eng.run()
    finally:
        obs.set_registry(prev)
    return done, reg


def _gen(done):
    return {r.uid: tuple(r.generated) for r in done}


# ---------------------------------------------------------------------------
# bucket resolution
# ---------------------------------------------------------------------------

def test_auto_buckets():
    assert resolve_prefill_buckets("auto", 64) == (16, 32, 64)
    assert resolve_prefill_buckets("auto", 128) == (16, 32, 64, 128)
    assert resolve_prefill_buckets("auto", 512) == (16, 32, 64, 128, 256,
                                                    384, 512)
    assert resolve_prefill_buckets(None, 64) is None
    assert resolve_prefill_buckets((), 64) is None
    assert resolve_prefill_buckets([64, 16], 64) == (16, 64)
    with pytest.raises(ValueError):
        resolve_prefill_buckets([144], 512)   # >128, not a 128-multiple
    with pytest.raises(ValueError):
        resolve_prefill_buckets([96], 64)     # above max_seq


def test_bucket_for_rounds_up_and_falls_back():
    b = (16, 32, 64)
    assert bucket_for(b, 1) == 16
    assert bucket_for(b, 16) == 16
    assert bucket_for(b, 17) == 32
    assert bucket_for(b, 65) == 65            # overflow: exact length
    assert bucket_for(None, 23) == 23


# ---------------------------------------------------------------------------
# per-slot cache writes (the zero-copy admission path)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_write_slot_prefix_no_stale_leak(dtype):
    """Reusing a slot must not leak the previous request's KV beyond the
    new prompt length — neither pad garbage inside the bucket nor stale
    tokens beyond it."""
    L, B, S, KH, D = 2, 3, 16, 2, 4
    cache = init_kv_cache(L, B, S, KH, D, dtype)
    # simulate a previous long request occupying slot 1
    stale = KVCache(jnp.full_like(cache.k, 7.0), jnp.full_like(cache.v, 9.0),
                    jnp.full((B,), S, jnp.int32), jnp.zeros((B,), jnp.int32))
    # new request: true length 3 padded into an 8-token bucket, store offset
    Sb, true_len, offset = 8, 3, 128
    k_new = jax.random.normal(KEY, (L, 1, Sb, KH * D), dtype)
    v_new = jax.random.normal(jax.random.fold_in(KEY, 1), (L, 1, Sb, KH * D),
                              dtype)
    slot_cache = KVCache(k_new, v_new, jnp.full((1,), true_len, jnp.int32),
                         jnp.full((1,), offset, jnp.int32))
    out = write_slot_prefix(stale, slot_cache, 1, true_len)
    # prefix [0, true_len) is the new KV
    np.testing.assert_array_equal(np.asarray(out.k[:, 1, :true_len]),
                                  np.asarray(k_new[:, 0, :true_len]))
    # everything beyond true_len is zero — no pad garbage, no stale KV
    assert not np.any(np.asarray(out.k[:, 1, true_len:]))
    assert not np.any(np.asarray(out.v[:, 1, true_len:]))
    assert int(out.length[1]) == true_len
    assert int(out.offset[1]) == offset
    # other slots untouched
    for s in (0, 2):
        np.testing.assert_array_equal(np.asarray(out.k[:, s]),
                                      np.asarray(stale.k[:, s]))
        assert int(out.length[s]) == S


def test_append_token_equals_per_slot_update():
    B, S, KH, D = 4, 6, 2, 3
    rng = np.random.default_rng(0)
    kc, vc = (jnp.asarray(rng.normal(size=(B, S, KH, D)), jnp.bfloat16)
              for _ in range(2))
    nk, nv = (jnp.asarray(rng.normal(size=(B, KH, D)), jnp.float32)
              for _ in range(2))
    lengths = jnp.array([0, 3, S - 1, S + 2], jnp.int32)   # last one clamps
    got_k, got_v = append_token(kc, vc, nk, nv, lengths)
    for b in range(B):
        for c, n, got in ((kc, nk, got_k), (vc, nv, got_v)):
            want = jax.lax.dynamic_update_slice_in_dim(
                c[b], n[b][None].astype(c.dtype), lengths[b], axis=0)
            np.testing.assert_array_equal(np.asarray(got[b], np.float32),
                                          np.asarray(want, np.float32))


def test_write_slot_prefix_matches_merge_reference():
    """For an exact-length (unpadded) prefix the in-place write equals the
    old full-copy merge on the written region."""
    from repro.serving.engine import _merge_slot_cache
    L, B, S, KH, D = 2, 4, 12, 2, 4
    cache = init_kv_cache(L, B, S, KH, D, jnp.float32)
    Sb = 5
    slot_cache = KVCache(
        jax.random.normal(KEY, (L, 1, Sb, KH * D)),
        jax.random.normal(jax.random.fold_in(KEY, 2), (L, 1, Sb, KH * D)),
        jnp.full((1,), Sb, jnp.int32), jnp.full((1,), 64, jnp.int32))
    a = write_slot_prefix(cache, slot_cache, 2, Sb)
    b = _merge_slot_cache(cache, slot_cache, 2)
    np.testing.assert_array_equal(np.asarray(a.k), np.asarray(b.k))
    np.testing.assert_array_equal(np.asarray(a.v), np.asarray(b.v))
    np.testing.assert_array_equal(np.asarray(a.length), np.asarray(b.length))
    np.testing.assert_array_equal(np.asarray(a.offset), np.asarray(b.offset))
    got = read_slot(a, 2)
    np.testing.assert_array_equal(np.asarray(got.k[:, 0, :Sb]),
                                  np.asarray(slot_cache.k[:, 0]))


def test_write_slot_prefix_donatable():
    """The write must be expressible as an in-place update: jit with
    donation consumes the batch cache and the result is correct."""
    L, B, S, KH, D = 1, 2, 8, 1, 4
    cache = init_kv_cache(L, B, S, KH, D, jnp.float32)
    slot_cache = KVCache(
        jnp.ones((L, 1, 4, KH * D)), 2 * jnp.ones((L, 1, 4, KH * D)),
        jnp.full((1,), 4, jnp.int32), jnp.zeros((1,), jnp.int32))
    wr = jax.jit(write_slot_prefix, donate_argnums=(0,))
    out = wr(cache, slot_cache, jnp.int32(1), jnp.int32(4))
    assert np.asarray(out.k[:, 1, :4]).all()
    with pytest.raises(RuntimeError):
        _ = np.asarray(cache.k)   # donated: input buffer was consumed


def test_engine_decode_matches_a_forward_without_cache():
    """Prefill then decode through the slotted lane-dense cache, over
    several waves that reuse both slots, serves at every step the token a
    float32 forward over the whole sequence, with no cache, ranks first
    (teacher-forced on the served tokens)."""
    import dataclasses
    from repro.models import dense
    cfg = dataclasses.replace(get_config("qwen1.5-0.5b").reduced(),
                              dtype="float32")
    params = build_model(cfg).init(KEY)
    eng = ServingEngine(cfg, params, EngineConfig(
        max_slots=2, max_seq=48, cache_dtype=jnp.float32))
    rng = np.random.default_rng(3)
    for n, new in ((5, 6), (11, 9), (3, 4), (17, 7), (8, 5)):
        eng.submit(rng.integers(0, cfg.vocab_size, n).tolist(), new)
    done = eng.run()
    assert len(done) == 5 and eng.metrics["decode_steps"] >= 9
    for r in done:
        seq = jnp.asarray(list(r.prompt) + list(r.generated), jnp.int32)
        x = dense.embed_inputs(cfg, params, seq[None])
        h, _ = dense.forward_hidden(cfg, params, x, jnp.arange(len(seq)),
                                    remat=False)
        logits = np.asarray(jnp.einsum("sd,vd->sv", h[0],
                                       dense.unembed_matrix(cfg, params)))
        ref = logits[len(r.prompt) - 1:len(seq) - 1]
        gap = ref.max(-1) - ref[np.arange(len(ref)), r.generated]
        assert len(r.generated) == len(ref) and gap.max() <= 1e-3, gap


# ---------------------------------------------------------------------------
# differential: donation + persistence + bucketing change nothing observable
# ---------------------------------------------------------------------------

REQS = [([3 + i] * (5 + 3 * i), 4, "laws") for i in range(5)]


def test_donated_persistent_equals_copying_path(tiny):
    cfg, params = tiny
    corpus = synthesize_corpus(CorpusSpec("laws", 256, cfg.vocab_size))
    donated, reg_d = _run(cfg, params,
                          EngineConfig(max_slots=3, max_seq=64),
                          REQS, [("laws", corpus)])
    copying, reg_c = _run(cfg, params,
                          EngineConfig(max_slots=3, max_seq=64,
                                       donate_cache=False),
                          REQS, [("laws", corpus)])
    assert _gen(donated) == _gen(copying)
    assert reg_d.gauge("engine/decode_cache_bytes_copied").value == 0
    assert reg_c.gauge("engine/decode_cache_bytes_copied").value > 0


def test_bucketed_prefill_equals_exact_prefill(tiny):
    """Pad + masked routing + dynamic logit index == exact-length prefill:
    the compile-count win must not change a single generated token."""
    cfg, params = tiny
    corpus = synthesize_corpus(CorpusSpec("laws", 256, cfg.vocab_size))
    bucketed, reg_b = _run(cfg, params,
                           EngineConfig(max_slots=3, max_seq=64),
                           REQS, [("laws", corpus)])
    exact, _ = _run(cfg, params,
                    EngineConfig(max_slots=3, max_seq=64,
                                 prefill_buckets=None),
                    REQS, [("laws", corpus)])
    assert _gen(bucketed) == _gen(exact)
    # 5 distinct prompt lengths (5, 8, 11, 14, 17) but <= 2 programs
    # (buckets 16 and 32)
    assert reg_b.gauge("engine/prefill_compile_count").value <= 2


def test_prefill_compile_count_bounded_by_buckets(tiny):
    """Prompt-length sweep: the prefill jit cache stops growing per prompt
    — at most one program per bucket."""
    cfg, params = tiny
    corpus = synthesize_corpus(CorpusSpec("laws", 256, cfg.vocab_size))
    lengths = [17, 18, 33, 34, 65, 66, 129, 130]
    reqs = [([2] * n, 2, "laws") for n in lengths]
    done, reg = _run(cfg, params,
                     EngineConfig(max_slots=2, max_seq=256), reqs,
                     [("laws", corpus)])
    assert len(done) == len(lengths)
    buckets = resolve_prefill_buckets("auto", 256)
    compiles = reg.gauge("engine/prefill_compile_count").value
    assert compiles <= len(buckets), (compiles, buckets)
    assert compiles == 4   # 17/18->32, 33/34->64, 65/66->128, 129/130->256


def test_run_callable_repeatedly_with_slot_reuse(tiny):
    """The persistent cache survives run() boundaries, and a reused slot
    (previously holding a longer request) decodes the same tokens as a
    fresh engine — no stale-KV bleed-through."""
    cfg, params = tiny
    corpus = synthesize_corpus(CorpusSpec("laws", 256, cfg.vocab_size))
    reg, prev = _fresh_registry()
    try:
        eng = ServingEngine(cfg, params, EngineConfig(max_slots=2,
                                                      max_seq=64))
        eng.register_corpus("laws", corpus)
        eng.submit([9] * 40, max_new_tokens=4, corpus_id="laws")  # long
        first = eng.run()
        assert len(first) == 1
        # second run reuses slot 0 with a much shorter prompt
        eng.submit([4, 5, 6], max_new_tokens=5, corpus_id="laws")
        second = [r for r in eng.run() if r.uid != first[0].uid]
    finally:
        obs.set_registry(prev)
    fresh, _ = _run(cfg, params, EngineConfig(max_slots=2, max_seq=64),
                    [([4, 5, 6], 5, "laws")], [("laws", corpus)])
    assert tuple(second[0].generated) == tuple(fresh[0].generated)


# ---------------------------------------------------------------------------
# mixed-corpus regression: corpus-B requests attend store B
# ---------------------------------------------------------------------------

def test_mixed_corpus_requests_decode_against_their_store(tiny):
    """Regression for the wrong-store decode: with corpora A and B
    interleaved in one stream, every B request must generate exactly what
    it generates on an engine that only ever saw store B."""
    cfg, params = tiny
    corpus_a = synthesize_corpus(CorpusSpec("A", 256, cfg.vocab_size,
                                            seed=1))
    corpus_b = synthesize_corpus(CorpusSpec("B", 256, cfg.vocab_size,
                                            seed=2))
    ecfg = EngineConfig(max_slots=3, max_seq=64)
    b_prompts = [[7, 8, 9, 10], [11, 12, 13]]
    mixed_reqs = [([1] * 6, 4, "A"), (b_prompts[0], 4, "B"),
                  ([2] * 6, 4, "A"), (b_prompts[1], 4, "B"),
                  ([3] * 6, 4, "A")]
    mixed, _ = _run(cfg, params, ecfg, mixed_reqs,
                    [("A", corpus_a), ("B", corpus_b)])
    only_b, _ = _run(cfg, params, ecfg,
                     [(p, 4, "B") for p in b_prompts], [("B", corpus_b)])
    got_b = sorted(tuple(r.generated) for r in mixed
                   if r.corpus_id == "B")
    want_b = sorted(tuple(r.generated) for r in only_b)
    assert got_b == want_b
    # and the A requests all finished too
    assert sum(r.corpus_id == "A" for r in mixed) == 3


# ---------------------------------------------------------------------------
# livelock + submit-time validation through the engine
# ---------------------------------------------------------------------------

def test_run_raises_instead_of_livelock(tiny):
    cfg, params = tiny
    reg, prev = _fresh_registry()
    try:
        # budget below one slot's cost: nothing is ever admissible
        eng = ServingEngine(cfg, params,
                            EngineConfig(max_slots=2, max_seq=64,
                                         mem_budget_bytes=1.0))
        eng.submit([1, 2, 3], max_new_tokens=2)
        with pytest.raises(RuntimeError, match="livelock"):
            eng.run()
        assert reg.counter("scheduler/admission_deferred_mem").value >= 1
    finally:
        obs.set_registry(prev)


def test_zero_new_tokens_rejected_and_one_token_finishes(tiny):
    cfg, params = tiny
    eng = ServingEngine(cfg, params, EngineConfig(max_slots=1, max_seq=32))
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit([1, 2, 3], max_new_tokens=0)
    # max_new_tokens=1: the prefill's token finishes the request; no decode
    # wave runs and remaining never goes negative
    eng.submit([1, 2, 3], max_new_tokens=1)
    done = eng.run()
    assert len(done) == 1
    assert len(done[0].generated) == 1
    assert done[0].remaining == 0
    assert eng.metrics["decode_steps"] == 0
