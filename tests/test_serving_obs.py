"""What the serving engine counts and times, and where it counts it.

  * the served programs (slotted and paged decode, bucketed and chunked
    prefill) carry no host callback when a store is attached: the shared
    path's dispatch counts come back as step outputs and are recorded on
    the host with the tokens;
  * those counts: L·G·K routes dispatched per decode step on a dropless
    configuration, one utilization observation per layer per program call,
    and drops filed per layer when the capacity forces them;
  * the engine's spans (schedule, prefill, decode dispatch and wait, record,
    wave hooks) and ``engine/host_step_s``, in the registry and in a
    profiler trace;
  * request lifecycle stamps and ``scheduler/admit_wait_s``;
  * named scopes in the programs leave the lowered program unchanged.
"""
import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_config
from repro.core.scheduler import Request, latency_stats
from repro.data.pipeline import CorpusSpec, synthesize_corpus
from repro.models import dense
from repro.models.model import build_model
from repro.serving.engine import EngineConfig, ServingEngine

KEY = jax.random.PRNGKey(0)
CORPUS = "laws"


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("tinyllama-1.1b").reduced()
    params = build_model(cfg).init(KEY)
    corpus = synthesize_corpus(CorpusSpec(CORPUS, 256, cfg.vocab_size))
    return cfg, params, corpus


@pytest.fixture()
def reg():
    r = obs.MetricsRegistry()
    prev = obs.set_registry(r)
    try:
        yield r
    finally:
        obs.set_registry(prev)


def _engine(tiny, prompts=(), max_new=3, **kw):
    cfg, params, corpus = tiny
    ecfg = dict(max_slots=3, max_seq=64)
    ecfg.update(kw)
    eng = ServingEngine(cfg, params, EngineConfig(**ecfg))
    eng.register_corpus(CORPUS, corpus)
    for p in prompts:
        eng.submit(p, max_new_tokens=max_new, corpus_id=CORPUS)
    return eng


PROMPTS = [[5, 6, 7, 8], list(range(1, 12)), [9] * 7]


# ---------------------------------------------------------------------------
# no host callback in a served program
# ---------------------------------------------------------------------------

def _lowered(eng, program):
    store = eng.stores[CORPUS]
    start = jnp.asarray(store.total_tokens, jnp.int32)
    if program == "decode":
        return eng._decode.lower(eng.params, jnp.zeros((3,), jnp.int32),
                                 eng._ensure_cache(), store, True)
    if program == "prefill":
        return eng._prefill.lower(eng.params, jnp.zeros((1, 16), jnp.int32),
                                  jnp.asarray(5, jnp.int32), start, store,
                                  True)
    if program == "decode_paged":
        tbl, lens, offs = eng._tables.device_args()
        return eng._decode_paged.lower(
            eng.params, jnp.zeros((3,), jnp.int32), eng._ensure_pool(),
            jnp.asarray(tbl), jnp.asarray(lens), jnp.asarray(offs), store,
            True)
    ctx = eng.model.init_cache(1, 256, eng.ecfg.cache_dtype)
    return eng._prefill_chunked.lower(
        eng.params, jnp.zeros((1, 128), jnp.int32), ctx, start,
        jnp.asarray(100, jnp.int32), store, True)


@pytest.mark.parametrize("program", ["decode", "prefill", "decode_paged",
                                     "prefill_chunk"])
def test_served_programs_carry_no_host_callback(tiny, reg, program):
    layout = "paged" if program in ("decode_paged", "prefill_chunk") \
        else "slotted"
    eng = _engine(tiny, kv_layout=layout)
    text = _lowered(eng, program).as_text()
    assert "callback" not in text.lower()


# ---------------------------------------------------------------------------
# dispatch counts as step outputs
# ---------------------------------------------------------------------------

def test_decode_step_returns_per_layer_dispatch_stats(tiny):
    """Dropless (each chunk's capacity holds every group): each layer
    dispatches all G·K routes and drops none."""
    cfg, params, _ = tiny
    eng = _engine(tiny)
    store = eng.stores[CORPUS]
    G = 3
    cache = eng.model.init_cache(G, 64)
    toks = jnp.asarray([1, 2, 3], jnp.int32)
    logits, _, stats = dense.decode_step(cfg, params, toks, cache,
                                         store=store, return_stats=True)
    L, K = cfg.num_layers, cfg.moska.top_k_chunks
    assert stats.dispatched.shape == (L,)
    np.testing.assert_array_equal(stats.dispatched, G * K)
    np.testing.assert_array_equal(stats.dropped, 0)
    assert np.all((0 < stats.fill) & (stats.fill <= 1))
    # without a store: no stats; without the flag: the two-tuple
    assert dense.decode_step(cfg, params, toks, cache,
                             return_stats=True)[2] is None
    plain, _ = dense.decode_step(cfg, params, toks, cache, store=store)
    np.testing.assert_array_equal(plain, logits)


def test_engine_records_dispatch_per_layer_per_call(tiny, reg):
    cfg, _, _ = tiny
    eng = _engine(tiny, PROMPTS)
    eng.run()
    L, K, B = cfg.num_layers, cfg.moska.top_k_chunks, 3
    steps = int(reg.counter("engine/decode_steps").value)
    prefills = int(reg.counter("engine/prefills").value)
    assert steps > 0 and prefills == len(PROMPTS)
    # every prompt fits the 16-token bucket: one route group per prefill
    calls = steps + prefills
    util = reg.get("moska/dispatch_capacity_utilization")
    assert util.count == L * calls
    assert reg.counter("moska/dispatched_queries").value == \
        L * K * (B * steps + prefills)
    assert reg.counter("moska/dropped_queries").value == 0
    for i in range(L):
        h = reg.get(f"moska/dispatch_capacity_utilization_by_layer/L{i}")
        assert h.count == calls
        assert reg.counter(f"moska/dropped_queries_by_layer/L{i}").value == 0


def test_forced_drops_are_filed_per_layer(tiny, reg):
    """12 slots, two live: the ten idle slots route the same stale query
    into the same chunks, past a capacity of 8."""
    cfg, params, corpus = tiny
    cfg = dataclasses.replace(cfg, moska=dataclasses.replace(
        cfg.moska, query_capacity_factor=0.25))
    eng = _engine((cfg, params, corpus), PROMPTS[:2], max_slots=12)
    eng.run()
    dropped = reg.counter("moska/dropped_queries").value
    assert dropped > 0
    per_layer = sum(
        reg.counter(f"moska/dropped_queries_by_layer/L{i}").value
        for i in range(cfg.num_layers))
    assert per_layer == dropped


def test_paged_engine_records_chunked_prefill_per_chunk(tiny, reg):
    """A 200-token prompt prefills in two 128-token chunks: two program
    calls, each with one observation per layer."""
    cfg, _, _ = tiny
    eng = _engine(tiny, [list(range(1, 201))], max_new=2, max_slots=2,
                  kv_layout="paged", block_size=16)
    eng.run()
    steps = int(reg.counter("engine/decode_steps").value)
    chunks = int(reg.counter("engine/prefill_chunks").value)
    assert chunks == 2
    assert reg.get("moska/dispatch_capacity_utilization").count == \
        cfg.num_layers * (steps + chunks)


# ---------------------------------------------------------------------------
# spans, host step time, lifecycle stamps
# ---------------------------------------------------------------------------

ENGINE_SPANS = ("engine.schedule", "engine.prefill", "engine.decode_dispatch",
                "engine.decode_wait", "engine.record", "engine.wave_hooks")


@pytest.mark.parametrize("layout,overlap", [("slotted", True),
                                            ("paged", True),
                                            ("paged", False)])
def test_engine_spans_and_host_step(tiny, reg, layout, overlap):
    eng = _engine(tiny, PROMPTS, kv_layout=layout, overlap_waves=overlap)
    eng.run()
    by_name = {}
    for sp in reg.spans:
        by_name.setdefault(sp.name, []).append(sp)
    for name in ENGINE_SPANS:
        assert name in by_name, name
        assert {sp.parent for sp in by_name[name]} == {"engine.run"}, name
    assert sorted(sp.attrs["uid"] for sp in by_name["engine.prefill"]) == \
        [0, 1, 2]
    steps = reg.counter("engine/decode_steps").value
    host = reg.get("engine/host_step_s")
    assert host.count == steps == len(by_name["engine.decode_wait"])
    # the engine's own time: schedule + dispatch of each wave and the
    # record (and, paged, the table bookkeeping) of the wave before it;
    # prefills and hooks are left out
    own = sum(sp.duration_s for n in ("engine.schedule",
                                      "engine.decode_dispatch",
                                      "engine.record",
                                      "engine.wave_bookkeeping")
              for sp in by_name.get(n, ()))
    assert 0 < host.sum <= own
    if layout == "paged" and not overlap:
        bk = by_name["engine.wave_bookkeeping"]
        assert {sp.parent for sp in bk} == {"engine.run"}
        assert len(bk) == steps
        assert host.sum >= sum(sp.duration_s for sp in bk[:-1])
    else:
        assert "engine.wave_bookkeeping" not in by_name


def test_engine_spans_land_in_the_profiler_trace(tiny, reg, tmp_path):
    from jax.profiler import ProfileData
    eng = _engine(tiny, PROMPTS[:1])
    eng.run()                      # compile outside the trace
    eng.submit([3, 4, 5], max_new_tokens=3, corpus_id=CORPUS)
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    pd = ProfileData.from_file(str(path))
    names = {ev.name for p in pd.planes for line in p.lines
             for ev in line.events}
    assert "engine.decode_wait" in names
    assert "engine.prefill" in names


def test_request_lifecycle_stamps_are_ordered(tiny, reg):
    eng = _engine(tiny, PROMPTS + [[2, 3]] * 2)   # 5 requests, 3 slots
    done = eng.run()
    assert len(done) == 5
    for r in done:
        assert r.arrival <= r.admitted_at <= r.first_token_at \
            <= r.finished_at
    waits = reg.get("scheduler/admit_wait_s")
    assert waits.count == 5
    assert waits.sum == pytest.approx(
        sum(r.admitted_at - r.arrival for r in done))
    st = latency_stats(done)
    ttft = sorted(r.first_token_at - r.arrival for r in done)
    assert st["ttft_p50_s"] == ttft[2] and st["ttft_p95_s"] == ttft[4]
    assert st["latency_p95_s"] == max(r.finished_at - r.arrival for r in done)
    assert latency_stats([Request(0, [1], 1)]) == dict.fromkeys(st, 0.0)


# ---------------------------------------------------------------------------
# named scopes are metadata only
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_named_scopes_leave_the_program_unchanged(tiny, monkeypatch,
                                                  program):
    cfg, params, _ = tiny
    eng = _engine(tiny)
    store = eng.stores[CORPUS]

    def lowered():
        if program == "decode":
            def f(p, t, c, s):
                return dense.decode_step(cfg, p, t, c, store=s,
                                         return_stats=True)
            args = (jnp.zeros((3,), jnp.int32), eng.model.init_cache(3, 64))
        else:
            def f(p, t, c, s):
                return dense.prefill(cfg, p, t, c, store=s,
                                     true_len=jnp.asarray(5),
                                     return_stats=True)
            args = (jnp.zeros((1, 16), jnp.int32),
                    eng.model.init_cache(1, 16))
        return jax.jit(f).lower(params, *args, store).as_text()

    scoped = lowered()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    assert lowered() == scoped
