"""Unit tests for the observability layer (repro.obs) and the LSE-merge
kernel edge cases it keeps honest.

Covers: histogram bucketing (edges, overflow, quantiles), counter/gauge
semantics, span nesting (parent/depth) and the span ring, exporter
round-trip (JSON and line protocol), debug recording through
jax.debug.callback, dispatch counts returned by the shared path, the
backend-compile counter, and
kernels/lse_merge.py on all-(-inf) LSE rows and merge associativity.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.kernels.lse_merge import NEG_INF, lse_merge


@pytest.fixture()
def fresh_registry():
    """Isolated registry + restored jit-metrics flag per test."""
    reg = obs.MetricsRegistry()
    prev_reg = obs.set_registry(reg)
    prev_flag = obs.metrics.JIT_METRICS
    try:
        yield reg
    finally:
        obs.set_registry(prev_reg)
        obs.enable_jit_metrics(prev_flag)


# ---------------------------------------------------------------------------
# metrics primitives
# ---------------------------------------------------------------------------

def test_counter_and_gauge(fresh_registry):
    reg = fresh_registry
    reg.inc("c")
    reg.inc("c", 2.5)
    assert reg.counter("c").value == 3.5
    with pytest.raises(ValueError):
        reg.counter("c").inc(-1)
    reg.set_gauge("g", 5)
    reg.set_gauge("g", -2)
    g = reg.gauge("g")
    assert (g.value, g.min, g.max, g.updates) == (-2.0, -2.0, 5.0, 2)
    with pytest.raises(TypeError):
        reg.gauge("c")          # kind mismatch


def test_histogram_bucketing(fresh_registry):
    h = fresh_registry.histogram("h", edges=(1.0, 2.0, 5.0))
    for v in (0.5, 1.0, 1.5, 2.0, 4.9, 5.0, 7.0, 100.0):
        h.observe(v)
    # v <= edge lands in that bucket; > last edge overflows
    assert h.counts == [2, 2, 2, 2]
    assert h.count == 8
    assert h.sum == pytest.approx(121.9)
    assert (h.min, h.max) == (0.5, 100.0)
    assert h.mean == pytest.approx(121.9 / 8)
    assert h.quantile(0.25) == 1.0
    assert h.quantile(1.0) == 100.0     # overflow bucket reports max
    with pytest.raises(ValueError):
        fresh_registry.histogram("bad", edges=(2.0, 1.0))


def test_histogram_snapshot_shape(fresh_registry):
    h = fresh_registry.histogram("h", edges=obs.FRACTION_EDGES)
    h.observe(0.35)
    snap = h.snapshot()
    assert len(snap["counts"]) == len(snap["edges"]) + 1
    assert sum(snap["counts"]) == snap["count"] == 1


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def test_span_nesting(fresh_registry):
    reg = fresh_registry
    with obs.span("outer", registry=reg):
        assert obs.current_span().name == "outer"
        with obs.span("inner", registry=reg, wave=3):
            assert obs.current_span().depth == 1
    assert obs.current_span() is None
    by_name = {s.name: s for s in reg.spans}
    assert by_name["inner"].parent == "outer"
    assert by_name["inner"].depth == 1
    assert by_name["outer"].parent is None
    assert by_name["inner"].attrs == {"wave": 3}
    assert by_name["outer"].duration_s >= by_name["inner"].duration_s >= 0
    # spans auto-feed latency histograms
    assert reg.histogram("span/outer/duration_s").count == 1


def test_span_records_on_exception(fresh_registry):
    reg = fresh_registry
    with pytest.raises(RuntimeError):
        with obs.span("boom", registry=reg):
            raise RuntimeError("x")
    assert [s.name for s in reg.spans] == ["boom"]
    assert obs.current_span() is None       # stack unwound


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

def test_exporter_json_round_trip(fresh_registry, tmp_path):
    reg = fresh_registry
    reg.inc("scheduler/admitted", 4)
    reg.set_gauge("scheduler/slot_occupancy", 0.75)
    reg.observe("engine/decode_step_latency_s", 0.003)
    with obs.span("engine.run", registry=reg):
        pass
    path = str(tmp_path / "m.json")
    obs.dump(path, reg)
    back = obs.load(path)
    assert back.snapshot() == reg.snapshot()
    assert [s.name for s in back.spans] == [s.name for s in reg.spans]
    # and via the in-memory dict path too
    assert obs.from_dict(obs.to_dict(reg)).snapshot() == reg.snapshot()


def test_exporter_rejects_unknown_schema(fresh_registry):
    with pytest.raises(ValueError):
        obs.from_dict({"schema_version": 999, "metrics": {}})


def test_line_protocol(fresh_registry, tmp_path):
    reg = fresh_registry
    reg.inc("tokens", 12)
    reg.observe("lat", 0.2, edges=(0.1, 1.0))
    lines = obs.to_lines(reg)
    assert "tokens value=12.0" in lines
    assert "lat,le=1.0 count=1" in lines
    assert any(line.startswith("lat count=1 sum=0.2") for line in lines)
    path = str(tmp_path / "m.lp")
    obs.dump(path, reg)
    assert open(path).read().strip() == "\n".join(lines)


# ---------------------------------------------------------------------------
# jit-safe recording
# ---------------------------------------------------------------------------

def test_jit_metrics_record_per_execution(fresh_registry):
    reg = fresh_registry
    obs.enable_jit_metrics(True)

    @jax.jit
    def f(x):
        obs.jit_inc("jit/calls", 1)
        obs.jit_observe("jit/mean", jnp.mean(x), edges=obs.FRACTION_EDGES)
        return x + 1

    for _ in range(3):
        f(jnp.full((4,), 0.5)).block_until_ready()
    # trace-time-only recording would show 1; per-execution shows 3
    assert reg.counter("jit/calls").value == 3
    assert reg.histogram("jit/mean", obs.FRACTION_EDGES).count == 3


def test_jit_metrics_disabled_is_noop(fresh_registry):
    reg = fresh_registry
    obs.enable_jit_metrics(False)

    @jax.jit
    def f(x):
        obs.jit_inc("jit/calls", 1)
        return x + 1

    f(jnp.zeros((2,))).block_until_ready()
    assert reg.get("jit/calls") is None


def _routed_inputs(G=4, K=2, E=4, C=8, H=8, KH=2, D=16):
    from repro.core.router import Routing
    key = jax.random.PRNGKey(0)
    k = jax.random.normal(jax.random.fold_in(key, 1), (E, KH, C, D))
    v = jax.random.normal(jax.random.fold_in(key, 2), (E, KH, C, D))
    q = jax.random.normal(jax.random.fold_in(key, 3), (G, 1, H, D))
    ids = jnp.tile(jnp.arange(K, dtype=jnp.int32)[None], (G, 1))
    return q, k, v, Routing(ids, jnp.zeros((G, K)), jnp.zeros((G, E)))


def test_dispatch_metrics_flow_from_shared_attention(fresh_registry):
    """shared_attention_batched returns the dispatch counts the serving
    engine exports, and the engine files them under the moska/* names."""
    from repro.core.shared_attention import shared_attention_batched
    from repro.serving.engine import record_dispatch
    reg = fresh_registry
    G, K, E = 4, 2, 4
    q, k, v, r = _routed_inputs(G, K, E)
    part = jax.jit(lambda q: shared_attention_batched(
        q, k, v, r, capacity=G * K))(q)
    st = jax.device_get(part.stats)
    # every group routes to chunks 0 and 1: 2·G of E·G·K slots filled
    assert float(st.fill) == pytest.approx(2 * G / (E * G * K))
    assert (int(st.dispatched), int(st.dropped)) == (G * K, 0)
    record_dispatch(reg, jax.tree.map(lambda x: np.asarray(x)[None], st))
    util = reg.get("moska/dispatch_capacity_utilization")
    assert util is not None and util.count == 1
    assert reg.counter("moska/dispatched_queries").value == G * K
    assert reg.counter("moska/dropped_queries").value == 0


def test_per_layer_dispatch_metrics_from_shared_attention(fresh_registry):
    """Stats stacked by a layer scan come back ``(L,)``; the engine files
    utilization and dropped-query counts under per-layer names as well as
    the totals. Layer 1's capacity of 1 drops routes."""
    from repro.core.shared_attention import shared_attention_batched
    from repro.serving.engine import record_dispatch
    reg = fresh_registry
    G, K = 4, 2
    q, k, v, r = _routed_inputs(G, K)

    def layer(cap):
        return shared_attention_batched(q, k, v, r, capacity=cap).stats

    st = jax.device_get([layer(G * K), layer(1), layer(G * K)])
    stacked = jax.tree.map(lambda *xs: np.stack(xs), *st)
    assert stacked.dropped.shape == (3,)
    record_dispatch(reg, stacked)
    for i in range(3):
        util = reg.get(f"moska/dispatch_capacity_utilization_by_layer/L{i}")
        assert util is not None and util.count == 1
    drops = [reg.counter(f"moska/dropped_queries_by_layer/L{i}").value
             for i in range(3)]
    assert drops[0] == drops[2] == 0
    # capacity 1 keeps one route per chunk: K chunks kept, the rest drop
    assert drops[1] == G * K - K
    assert reg.counter("moska/dropped_queries").value == sum(drops)
    assert reg.counter("moska/dispatched_queries").value == \
        3 * G * K - sum(drops)
    assert reg.get("moska/dispatch_capacity_utilization").count == 3


def test_span_ring_keeps_the_most_recent(fresh_registry):
    reg = fresh_registry
    n = obs.MAX_SPANS + 5
    for i in range(n):
        with obs.span("s", registry=reg, record_histogram=False, i=i):
            pass
    assert len(reg.spans) == obs.MAX_SPANS
    assert reg.spans[0].attrs["i"] == 5
    assert reg.spans[-1].attrs["i"] == n - 1
    assert len(obs.to_dict(reg)["spans"]) == obs.MAX_SPANS


def test_fresh_jit_counts_a_backend_compile(fresh_registry):
    reg = fresh_registry
    x = jnp.ones((3,))
    obs.watch_compiles()
    assert reg.counter("jax/backend_compiles").value == 0
    c = float(np.random.default_rng().random())   # a program not compiled
    jax.jit(lambda x: x * c + 1.0)(x).block_until_ready()
    assert reg.counter("jax/backend_compiles").value == 1
    assert reg.counter("jax/backend_compile_s").value > 0


def test_persistent_cache_load_is_not_a_compile(fresh_registry, tmp_path):
    from jax.experimental.compilation_cache import compilation_cache as cc
    reg = fresh_registry
    obs.watch_compiles()
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_enable_compilation_cache")
    prev = {k: getattr(jax.config, k) for k in keys}
    try:
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        cc.reset_cache()

        def f(x):
            return jnp.sin(x) * 3.25 + 0.5

        x = jnp.ones((5,))
        n0 = reg.counter("jax/backend_compiles").value
        jax.jit(f)(x).block_until_ready()
        assert reg.counter("jax/backend_compiles").value == n0 + 1
        jax.clear_caches()          # next call loads from the disk cache
        jax.jit(f)(x).block_until_ready()
        assert reg.counter("jax/backend_compiles").value == n0 + 1
    finally:
        for k, v in prev.items():
            jax.config.update(k, v)
        cc.reset_cache()


def test_streaming_exporter_flush_cadence(fresh_registry, tmp_path):
    """StreamingExporter flushes every Nth tick, atomically, and the
    on-disk snapshot tracks the registry state at flush time."""
    reg = fresh_registry
    path = str(tmp_path / "live.json")
    exp = obs.StreamingExporter(path, every=2, reg=reg)
    with pytest.raises(ValueError):
        obs.StreamingExporter(path, every=0)

    reg.inc("waves")
    assert exp.tick() is False          # tick 1: no flush yet
    import os
    assert not os.path.exists(path)
    reg.inc("waves")
    assert exp.tick() is True           # tick 2: flush
    assert obs.load(path).counter("waves").value == 2
    assert not os.path.exists(path + ".tmp")    # atomic replace completed
    reg.inc("waves")
    exp.tick()
    assert obs.load(path).counter("waves").value == 2   # tick 3: stale
    exp.tick()
    assert obs.load(path).counter("waves").value == 3   # tick 4: fresh
    assert (exp.ticks, exp.flushes) == (4, 2)


# ---------------------------------------------------------------------------
# kernels/lse_merge.py edge cases
# ---------------------------------------------------------------------------

def _ref_merge(outs, lses):
    m = np.max(lses, axis=0)
    w = np.exp(lses - m[None])
    denom = np.sum(w, axis=0)
    out = np.sum(outs * w[..., None], axis=0) / np.maximum(
        denom, 1e-37)[..., None]
    return out, m + np.log(np.maximum(denom, 1e-37))


def test_lse_merge_matches_reference():
    key = jax.random.PRNGKey(1)
    P, N, H, D = 3, 8, 4, 16
    outs = jax.random.normal(jax.random.fold_in(key, 1), (P, N, H, D))
    lses = jax.random.normal(jax.random.fold_in(key, 2), (P, N, H))
    out, lse = lse_merge(outs, lses)
    ref_o, ref_l = _ref_merge(np.asarray(outs), np.asarray(lses))
    np.testing.assert_allclose(out, ref_o, rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(lse, ref_l, rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("sentinel", [NEG_INF, -np.inf])
def test_lse_merge_all_empty_rows(sentinel):
    """Rows where every partial is empty (-inf LSE): output must be
    finite (zero) and the merged LSE must stay at the sentinel floor."""
    P, N, H, D = 2, 4, 2, 8
    outs = jnp.zeros((P, N, H, D))
    lses = jnp.full((P, N, H), sentinel, jnp.float32)
    out, lse = lse_merge(outs, lses)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_array_equal(np.asarray(out), 0.0)
    assert np.all(np.asarray(lse) <= NEG_INF / 2)
    assert np.isfinite(np.asarray(lse)).all()


def test_lse_merge_partial_empty_rows():
    """Mixing one empty partial with finite ones must equal merging the
    finite ones alone."""
    key = jax.random.PRNGKey(2)
    N, H, D = 6, 2, 8
    o1 = jax.random.normal(jax.random.fold_in(key, 1), (N, H, D))
    o2 = jax.random.normal(jax.random.fold_in(key, 2), (N, H, D))
    l1 = jax.random.normal(jax.random.fold_in(key, 3), (N, H))
    l2 = jax.random.normal(jax.random.fold_in(key, 4), (N, H))
    empty_o = jnp.zeros((N, H, D))
    empty_l = jnp.full((N, H), -jnp.inf)
    out3, lse3 = lse_merge(jnp.stack([o1, o2, empty_o]),
                           jnp.stack([l1, l2, empty_l]))
    out2, lse2 = lse_merge(jnp.stack([o1, o2]), jnp.stack([l1, l2]))
    np.testing.assert_allclose(out3, out2, rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(lse3, lse2, rtol=3e-5, atol=3e-5)


def test_lse_merge_associativity():
    """merge(merge(a, b), c) == merge(a, b, c) to fp32 tolerance."""
    key = jax.random.PRNGKey(3)
    N, H, D = 5, 2, 8
    parts = [(jax.random.normal(jax.random.fold_in(key, 10 + i),
                                (N, H, D)),
              5.0 * jax.random.normal(jax.random.fold_in(key, 20 + i),
                                      (N, H)))
             for i in range(3)]
    o_all, l_all = lse_merge(jnp.stack([p[0] for p in parts]),
                             jnp.stack([p[1] for p in parts]))
    o_ab, l_ab = lse_merge(jnp.stack([parts[0][0], parts[1][0]]),
                           jnp.stack([parts[0][1], parts[1][1]]))
    o_fin, l_fin = lse_merge(jnp.stack([o_ab, parts[2][0]]),
                             jnp.stack([l_ab, parts[2][1]]))
    np.testing.assert_allclose(o_fin, o_all, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(l_fin, l_all, rtol=1e-4, atol=1e-4)
