"""AP telemetry subsystem: tracer invariants, metrics quantiles, Perfetto
export schema, and the no-overhead / bit-exactness contracts.

Acceptance contract (ISSUE 6):

- span nesting/ordering: every closed span carries its parent, child
  intervals nest inside the parent's, misnested exits raise;
- Histogram.quantile matches numpy.percentile (linear interpolation) on
  the retained window;
- to_chrome() round-trips through validate_chrome_trace: metadata first,
  "X" events with µs timestamps, model-time slices on pid 1;
- with tracing OFF the instrumented paths leave digits + APStats
  bit-identical (parity vs a traced and a profiled run);
- per-program attribution sums bit-exactly back to the APStats the same
  run aggregated (total_ap_stats == stats);
- compile front doors bump hit/miss counters in the metrics registry;
- Engine.ap_report raises (not silently zeroes) when the AP context was
  configured but never reached.
"""
import json

import numpy as np
import pytest

from repro import apc
from repro.apc import metrics, trace
from repro.core.ap import APStats


def _mac_inputs(R=24, K=12, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.integers(-3, 4, size=(R, K)).astype(np.int32)
    w = rng.integers(-1, 2, size=(R, K)).astype(np.int32)
    return x, w


# ---------------------------------------------------------------------------
# tracer core: spans, nesting, instants
# ---------------------------------------------------------------------------

def test_span_nesting_and_ordering():
    t = trace.Tracer()
    with trace.tracing(t):
        with trace.span("outer", cat="serve"):
            with trace.span("inner1", cat="pool") as s:
                s.set(k=1)
            with trace.span("inner2", cat="pool"):
                trace.instant("tick", cat="pool")
    spans = {e.name: e for e in t.events
             if isinstance(e, trace.SpanRecord)}
    assert set(spans) == {"outer", "inner1", "inner2"}
    outer, i1, i2 = spans["outer"], spans["inner1"], spans["inner2"]
    assert i1.parent == "outer" and i2.parent == "outer"
    assert outer.parent is None
    # children nest inside the parent interval, in issue order
    assert outer.ts_ns <= i1.ts_ns
    assert i1.ts_ns + i1.dur_ns <= i2.ts_ns + i2.dur_ns
    assert i2.ts_ns + i2.dur_ns <= outer.ts_ns + outer.dur_ns
    assert spans["inner1"].args["k"] == 1
    insts = [e for e in t.events if isinstance(e, trace.InstantRecord)]
    assert len(insts) == 1 and insts[0].name == "tick"


def test_misnested_span_exit_raises():
    t = trace.Tracer()
    with trace.tracing(t):
        a = t.span("a", cat="x")
        b = t.span("b", cat="x")
        a.__enter__()
        b.__enter__()
        with pytest.raises(RuntimeError):
            a.__exit__(None, None, None)      # b still open
        b.__exit__(None, None, None)
        a.__exit__(None, None, None)


def test_spans_are_noops_when_disabled():
    with trace.disabled():
        assert trace.current_tracer() is None
        with trace.span("x", cat="y") as s:
            assert s is None                  # null span yields None
        trace.instant("i", cat="y")           # must not raise


def test_env_toggle_controls_global_tracer(monkeypatch):
    monkeypatch.setenv(trace.TRACE_ENV, "0")
    trace.reset_global_tracer()
    assert trace.env_enabled() is False
    assert trace.current_tracer() is None
    monkeypatch.setenv(trace.TRACE_ENV, "1")
    trace.reset_global_tracer()
    assert trace.env_enabled() is True
    tr = trace.current_tracer()
    assert tr is not None and tr is trace.global_tracer()
    with trace.span("g", cat="x"):
        pass
    assert any(isinstance(e, trace.SpanRecord) and e.name == "g"
               for e in tr.events)
    monkeypatch.delenv(trace.TRACE_ENV)
    trace.reset_global_tracer()


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_histogram_quantiles_match_numpy_percentile():
    rng = np.random.default_rng(3)
    xs = rng.exponential(10.0, size=500)
    h = metrics.Histogram("h")
    for v in xs:
        h.observe(float(v))
    for q in (0.0, 0.25, 0.5, 0.9, 0.99, 1.0):
        assert h.quantile(q) == pytest.approx(
            np.percentile(xs, 100 * q), rel=1e-12)
    assert h.count == 500
    assert h.total == pytest.approx(xs.sum())


def test_histogram_window_bounds_memory():
    h = metrics.Histogram("h", max_samples=8)
    for v in range(100):
        h.observe(float(v))
    assert h.count == 100                     # exact even past the window
    assert h.min == 0.0 and h.max == 99.0
    # quantiles come from the retained (most recent) window
    assert h.quantile(0.0) >= 92.0


def test_histogram_snapshot_consistent_under_concurrent_observe():
    """snapshot() copies every field under one lock acquisition, so the
    returned dict is internally consistent even while observers hammer
    the histogram from other threads."""
    import threading
    h = metrics.Histogram("h")
    stop = threading.Event()

    def writer(seed):
        rng = np.random.default_rng(seed)
        while not stop.is_set():
            h.observe(float(rng.uniform(0.0, 100.0)))

    threads = [threading.Thread(target=writer, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    try:
        for _ in range(200):
            snap = h.snapshot()
            if snap["count"] == 0:
                continue
            assert snap["min"] <= snap["mean"] <= snap["max"]
            assert snap["min"] <= snap["p50"] <= snap["p99"] <= snap["max"]
            assert snap["sum"] == pytest.approx(
                snap["mean"] * snap["count"])
    finally:
        stop.set()
        for t in threads:
            t.join()
    final = h.snapshot()
    assert final["count"] == h.count


def test_registry_concurrent_8_threads():
    """8 threads bumping the same instruments: no lost updates, no
    get-or-create races (each name resolves to ONE instrument)."""
    import threading
    reg = metrics.MetricsRegistry()
    n_threads, n_iter = 8, 500
    barrier = threading.Barrier(n_threads)

    def worker(tid):
        barrier.wait()
        for i in range(n_iter):
            reg.counter("c").inc()
            reg.gauge(f"g{tid}").set(i)
            reg.histogram("h").observe(float(i))

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = reg.snapshot()
    assert snap["c"] == n_threads * n_iter
    assert snap["h"]["count"] == n_threads * n_iter
    for tid in range(n_threads):
        assert snap[f"g{tid}"] == n_iter - 1
    # text exposition renders cleanly after the stampede
    text = reg.to_prometheus()
    assert f"c_total {n_threads * n_iter}" in text


def test_to_prometheus_text_format():
    reg = metrics.MetricsRegistry()
    reg.counter("serve.slo.latency_breaches").inc(2)
    reg.gauge("pool.occupancy").set(0.75)
    h = reg.histogram("serve.request_ms")
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    reg.histogram("empty.hist")
    text = reg.to_prometheus()
    lines = text.splitlines()
    assert "# TYPE serve_slo_latency_breaches_total counter" in lines
    assert "serve_slo_latency_breaches_total 2" in lines
    assert "# TYPE pool_occupancy gauge" in lines
    assert "pool_occupancy 0.75" in lines
    assert "# TYPE serve_request_ms summary" in lines
    assert 'serve_request_ms{quantile="0.5"} 2.5' in lines
    assert "serve_request_ms_sum 10.0" in lines
    assert "serve_request_ms_count 4" in lines
    # empty histograms render sum/count but no quantile samples
    assert "empty_hist_count 0" in lines
    assert not any(l.startswith("empty_hist{") for l in lines)
    # names are sanitized to [a-zA-Z0-9_:] and values parse as floats
    for l in lines:
        if l.startswith("#"):
            continue
        name, val = l.rsplit(" ", 1)
        assert metrics._PROM_BAD.search(name.split("{")[0]) is None
        float(val)                        # must parse


def test_registry_types_and_reset():
    reg = metrics.MetricsRegistry()
    reg.counter("c").inc(3)
    reg.gauge("g").set(1.5)
    reg.histogram("h").observe(2.0)
    snap = reg.snapshot()
    assert snap["c"] == 3 and snap["g"] == 1.5
    assert snap["h"]["count"] == 1
    with pytest.raises(TypeError):
        reg.gauge("c")                        # name already a counter
    reg.reset()
    assert reg.snapshot() == {}


# ---------------------------------------------------------------------------
# Perfetto export
# ---------------------------------------------------------------------------

def test_chrome_export_schema_roundtrip():
    t = trace.Tracer()
    with trace.tracing(t):
        with trace.span("root", cat="serve", batch=1):
            with trace.span("child", cat="pool"):
                pass
            t.model_span("prog", track="arr0", start_ns=t.now_ns(),
                         dur_ns=2000, block=0)
            trace.instant("up", cat="pool")
    doc = t.to_chrome()
    events = trace.validate_chrome_trace(json.loads(json.dumps(doc)))
    xs = [e for e in events if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"root", "child", "prog"}
    by_name = {e["name"]: e for e in xs}
    assert by_name["root"]["pid"] == trace.HOST_PID
    assert by_name["prog"]["pid"] == trace.MODEL_PID
    assert by_name["child"]["args"]["parent"] == "root"
    # µs conversion: child inside root on the exported timeline too
    assert by_name["root"]["ts"] <= by_name["child"]["ts"]
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert {m["name"] for m in meta} >= {"process_name", "thread_name"}
    # metadata precedes all slice events
    first_x = next(i for i, e in enumerate(doc["traceEvents"])
                   if e["ph"] == "X")
    assert all(e["ph"] == "M" for e in doc["traceEvents"][:first_x])


def test_validate_chrome_trace_rejects_bad_docs():
    with pytest.raises(ValueError):
        trace.validate_chrome_trace({"traceEvents": []})
    with pytest.raises(ValueError):
        trace.validate_chrome_trace({"nope": 1})
    with pytest.raises(ValueError):
        trace.validate_chrome_trace(
            {"traceEvents": [{"ph": "X", "name": "a"}]})  # missing fields


def test_counter_events_roundtrip_chrome():
    t = trace.Tracer()
    t.counter("ap.power", track="power dev0/arr0", ts_ns=100.0,
              power_w=1.5, thermal_w=0.5)
    t.counter("ap.power.bank", track="power bank", ts_ns=200.0,
              total_w=2.0)
    doc = json.loads(json.dumps(t.to_chrome()))
    events = trace.validate_chrome_trace(doc)
    cs = [e for e in events if e["ph"] == "C"]
    assert len(cs) == 2
    by_name = {e["name"]: e for e in cs}
    assert by_name["ap.power"]["args"] == \
        {"power_w": 1.5, "thermal_w": 0.5}
    assert by_name["ap.power.bank"]["args"] == {"total_w": 2.0}
    # both ride the model (pid 1) timeline, on named counter tracks
    assert all(e["pid"] == trace.MODEL_PID for e in cs)
    assert by_name["ap.power"]["ts"] == pytest.approx(0.1)   # ns -> µs
    tids = {e["tid"] for e in cs}
    named = {m["args"]["name"] for m in doc["traceEvents"]
             if m["ph"] == "M" and m["name"] == "thread_name"
             and m["tid"] in tids}
    assert named == {"power dev0/arr0", "power bank"}


def test_counter_rejects_malformed_values():
    t = trace.Tracer()
    with pytest.raises(ValueError):
        t.counter("c", track="t", ts_ns=0.0)           # no series values
    with pytest.raises(TypeError):
        t.counter("c", track="t", ts_ns=0.0, v="high")  # non-numeric
    with pytest.raises(TypeError):
        t.counter("c", track="t", ts_ns=0.0, v=True)   # bools excluded


def test_validate_chrome_trace_rejects_malformed_counter_events():
    def doc(args):
        ev = {"ph": "C", "name": "c", "cat": "power", "pid": 1, "tid": 0,
              "ts": 1.0}
        if args is not None:
            ev["args"] = args
        return {"traceEvents": [ev]}

    with pytest.raises(ValueError):
        trace.validate_chrome_trace(doc(None))         # args missing
    with pytest.raises(ValueError):
        trace.validate_chrome_trace(doc({}))           # no series
    with pytest.raises(ValueError):
        trace.validate_chrome_trace(doc({"v": "hot"}))  # non-numeric
    with pytest.raises(ValueError):
        trace.validate_chrome_trace(doc({"v": True}))  # bool is not a sample
    # a well-formed counter passes
    events = trace.validate_chrome_trace(doc({"v": 1.0}))
    assert events[0]["ph"] == "C"


# ---------------------------------------------------------------------------
# instrumented paths: parity off, bit-exact attribution on
# ---------------------------------------------------------------------------

def _smoke_engine():
    """The 1-layer d_model=16 qwen3 smoke cut, its MLPs on a 4x64x64 bank."""
    import jax
    from repro.configs import get_smoke_config
    from repro.launch.mesh import make_smoke_mesh
    from repro.models import model as M
    from repro.models.quant import quantize_model_params
    from repro.serve.engine import Engine, ServeCfg
    base = get_smoke_config("qwen3-0.6b")
    cfg = base.with_(n_layers=1, d_model=16, d_ff=24, n_heads=2,
                     n_kv_heads=2, head_dim=8, vocab=32,
                     ternary=base.ternary.__class__(enabled=True))
    qparams = quantize_model_params(M.init_params(cfg, jax.random.PRNGKey(0)))
    pool = apc.ArrayPool(n_arrays=4, rows=64, cols=64)
    ctx = apc.APServeContext(apc.Runtime(pool), x_levels=7)
    return Engine(cfg, qparams, make_smoke_mesh(), ServeCfg(max_len=8),
                  ap_ctx=ctx)


@pytest.mark.parametrize("case", ["mac", "serve"])
def test_tracing_off_is_bit_identical_across_variants(case, profiled):
    """REPRO_AP_TRACE=0 parity: digits, APStats and served tokens are the
    same with no tracer and no profiler, under a Tracer, and under the
    profiler (which records the program's spans); the MAC's digits
    decode to the integer dot products."""
    x, w = _mac_inputs()
    radix, width, K = 3, 8, x.shape[1]
    engine = _smoke_engine() if case == "serve" else None
    outs, stats = [], []
    for mode in ("off", "tracer", "profiler"):
        if case == "mac":
            def go():
                st = APStats(radix=radix)
                pool = apc.ArrayPool(n_arrays=2, rows=16, cols=96)
                tiled = apc.compile_mac_tiled(radix, K, width, 4,
                                              max_cols=pool.cols)
                out = apc.run_mac_tiled(x, w, tiled, pool=pool, stats=st)
                return np.asarray(out), st
        else:
            def go():
                toks = engine.generate(np.array([[3]], np.int32), 2)
                return toks, engine.ap_ctx.stats
        guard = (trace.tracing(trace.Tracer()) if mode == "tracer"
                 else trace.disabled())
        with guard:
            if mode == "profiler":
                (out, st), spans = profiled(go)
                assert spans
            else:
                out, st = go()
        outs.append(out)
        stats.append(st)
    if case == "mac":
        assert np.array_equal(outs[0], (x * w).sum(axis=1))
    for o in outs[1:]:
        assert np.array_equal(outs[0], o)
    for st in stats[1:]:
        assert (st.sets, st.resets) == (stats[0].sets, stats[0].resets)
        assert st.n_compare_cycles == stats[0].n_compare_cycles
        assert st.n_write_cycles == stats[0].n_write_cycles
        assert np.array_equal(st.mismatch_hist, stats[0].mismatch_hist)


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_pool_spans_on_the_profiler_clock(profiled):
    """One pooled run over 3 blocks puts ap.pool.run around 3 launches
    (and a drain at each launch past the one-array bank's in-flight cap
    of 2), then the counter sync, properly nested on the calling
    thread."""
    import jax.numpy as jnp
    prog = apc.compile_named("add", 3, 4)
    pool = apc.ArrayPool(n_arrays=1, rows=16, cols=16)
    rng = np.random.default_rng(0)
    x = jnp.asarray(np.concatenate(
        [rng.integers(0, 3, (40, 8)), np.zeros((40, 1))], 1), jnp.int8)
    apc.run_pooled(x, prog, pool, stats=APStats(radix=3))      # compile
    with trace.disabled():
        _, spans = profiled(
            lambda: apc.run_pooled(x, prog, pool, stats=APStats(radix=3)))
    (thread, evs), = spans.items()
    names = [e[0] for e in evs]
    assert names == ["ap.pool.run", "ap.pool.launch", "ap.pool.launch",
                     "ap.pool.drain", "ap.pool.launch", "ap.pool.drain",
                     "ap.stats.sync"]
    run = evs[0]
    launches = [e for e in evs if e[0] == "ap.pool.launch"]
    assert all(_inside(e, run) for e in evs[1:6])
    assert all(a[2] <= b[1] for a, b in zip(launches, launches[1:]))
    assert run[2] <= evs[-1][1]                 # the sync follows the run


def _moe_layer(seed=0, t=3, e_all=8, k=3, held=(2, 6), d=8, ff=6):
    """A seeded MoE layer call's inputs: tokens, the router's top-k over
    all ``e_all`` experts, and the held share's stacks."""
    import jax
    import jax.numpy as jnp
    from repro.configs.base import MoECfg
    from repro.models.moe import _route
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(0, 1, (t, d)), jnp.float32)
    router = jnp.asarray(rng.normal(0, 1, (d, e_all)), jnp.float32)
    gates, ids = _route(x, router, MoECfg(n_experts=e_all, top_k=k,
                                          d_ff=ff, held=held))
    pool = apc.ArrayPool(n_arrays=2, rows=64, cols=96)
    ctx = apc.APServeContext(apc.Runtime(pool), x_levels=7)
    n = held[1] - held[0]
    stacks = [apc.APExpertStack.from_dense(
        jnp.asarray(rng.normal(0, 1, shape), jnp.float32), store=pool.resident)
        for shape in ((n, d, ff), (n, d, ff), (n, ff, d))]
    return ctx, x, ids, gates, stacks


def test_moe_spans_on_the_profiler_clock(profiled):
    """A MoE layer call opens ap.moe.route (the host sort of pairs),
    ap.moe.dispatch (graph builds) and ap.moe.combine (float combine) on
    the calling thread, in order, around the two graph runs."""
    import jax
    ctx, x, ids, gates, stacks = _moe_layer()
    call = lambda: apc.ap_moe_dispatch(ctx, x, ids, gates, *stacks,  # noqa
                                       jax.nn.silu, first=2)
    call()                                                     # compile
    with trace.disabled():
        _, spans = profiled(call)
    (thread, evs), = spans.items()
    moe = [e[0] for e in evs if e[0].startswith("ap.moe.")]
    assert moe == ["ap.moe.route", "ap.moe.dispatch", "ap.moe.combine",
                   "ap.moe.dispatch", "ap.moe.combine"]
    graphs = [e for e in evs if e[0] == "ap.runtime.run_graph"]
    first_dispatch, first_combine = [e for e in evs
                                     if e[0].startswith("ap.moe.")][1:3]
    assert len(graphs) == 2
    assert first_dispatch[2] <= graphs[0][1] <= graphs[0][2] \
        <= first_combine[1]


def test_moe_counters_count_routed_and_held_pairs():
    """moe.pairs_routed / moe.pairs_held count a seeded call's (token,
    expert) pairs, all and on the held share; a call with a held pair is
    one moe.layer_calls, one with none one moe.layers_empty."""
    import jax
    import jax.numpy as jnp
    reg = metrics.get_registry()
    ctx, x, ids, gates, stacks = _moe_layer(seed=1)
    e = np.asarray(ids)
    names = ("moe.pairs_routed", "moe.pairs_held", "moe.layer_calls",
             "moe.layers_empty")
    before = reg.counter_values(names)
    apc.ap_moe_dispatch(ctx, x, ids, gates, *stacks, jax.nn.silu, first=2)
    outside = jnp.asarray([[0, 1, 7]] * 3, jnp.int32)
    apc.ap_moe_dispatch(ctx, x, outside, gates, *stacks, jax.nn.silu,
                        first=2)
    after = reg.counter_values(names)
    got = {n: after[n] - before[n] for n in names}
    assert got == {"moe.pairs_routed": 2 * e.size,
                   "moe.pairs_held": int(((e >= 2) & (e < 6)).sum()),
                   "moe.layer_calls": 1, "moe.layers_empty": 1}
    assert got["moe.pairs_held"] > 0


def test_attribution_sums_bit_exactly_to_ap_stats():
    x, w = _mac_inputs(seed=5)
    radix, width, K = 3, 8, x.shape[1]
    st = APStats(radix=radix)
    pool = apc.ArrayPool(n_arrays=2, rows=16, cols=96)
    tiled = apc.compile_mac_tiled(radix, K, width, 4, max_cols=pool.cols)
    t = trace.Tracer()
    with trace.tracing(t):
        apc.run_mac_tiled(x, w, tiled, pool=pool, stats=st)
    tot = t.total_ap_stats(radix)
    assert tot.sets == st.sets and tot.resets == st.resets
    assert tot.n_compare_cycles == st.n_compare_cycles
    assert tot.n_write_cycles == st.n_write_cycles
    assert np.array_equal(tot.mismatch_hist, st.mismatch_hist)
    # every program labelled, under the "pool" phase
    phases = t.phase_totals()
    assert set(phases) == {"pool"}
    assert phases["pool"]["programs"] == len(t.attributions)
    assert phases["pool"]["write_cycles"] == st.n_write_cycles


def test_runtime_graph_attribution_and_model_timeline():
    x, w = _mac_inputs(seed=9)
    radix, width, K = 3, 8, x.shape[1]
    pool = apc.ArrayPool(n_arrays=2, rows=16, cols=96)
    rt = apc.Runtime(pool)
    tiled = apc.compile_mac_tiled(radix, K, width, 4, max_cols=pool.cols)
    st = APStats(radix=radix)
    t = trace.Tracer()
    with trace.tracing(t):
        rt.run_mac_graph([(x, w, tiled)], stats=st)
    tot = t.total_ap_stats(radix)
    assert tot.n_write_cycles == st.n_write_cycles
    assert tot.sets == st.sets and tot.resets == st.resets
    spans = [e for e in t.events if isinstance(e, trace.SpanRecord)]
    names = {s.name for s in spans}
    assert "run_graph" in names
    assert any(n.startswith("wavefront") for n in names)
    # model-time slices live on pid 1: pool block launches on arr* tracks,
    # the scheduler's per-node intervals on dev*/arr* tracks
    model = [s for s in spans if s.pid == trace.MODEL_PID]
    assert model
    assert any(s.track.startswith("dev") for s in model)
    assert any(s.track.startswith("arr") for s in model)
    gspan = next(s for s in spans if s.name == "run_graph")
    assert gspan.args["makespan_cycles"] <= gspan.args["sequential_cycles"]


def test_compile_cache_hit_miss_counters():
    reg = metrics.get_registry()
    apc.clear_compile_caches()
    reg.reset()
    apc.compile_named("add", 3, 6)
    apc.compile_named("add", 3, 6)
    snap = reg.snapshot()
    assert snap["compile.compile_named.misses"] == 1
    assert snap["compile.compile_named.hits"] == 1


def test_traced_compile_emits_span_only_on_miss():
    apc.clear_compile_caches()
    t = trace.Tracer()
    with trace.tracing(t):
        apc.compile_named("add", 3, 7)
        apc.compile_named("add", 3, 7)
    spans = [e for e in t.events if isinstance(e, trace.SpanRecord)
             and e.cat == "compile"]
    # misses (compile_named + its nested compile_steps) get spans; the
    # second call is a hit and downgrades to an instant
    assert spans and all(s.args["cache"] == "miss" for s in spans)
    assert sum(s.name.startswith("compile:add") for s in spans) == 1
    hits = [e for e in t.events if isinstance(e, trace.InstantRecord)
            and e.name.startswith("compile_hit:add")]
    assert len(hits) == 1


# ---------------------------------------------------------------------------
# engine report guard
# ---------------------------------------------------------------------------

def test_ap_report_raises_when_request_bypassed_ap():
    from repro.serve.engine import Engine
    eng = Engine.__new__(Engine)              # no heavy model construction
    eng.ap_ctx = None
    assert eng.ap_report() is None
    pool = apc.ArrayPool(n_arrays=2, rows=16, cols=96)
    eng.ap_ctx = apc.APServeContext(apc.Runtime(pool), x_levels=7)
    with pytest.raises(RuntimeError, match="bypassed ap_serving"):
        eng.ap_report()


@pytest.mark.slow
def test_engine_request_under_env_toggle_emits_valid_trace(monkeypatch):
    """The acceptance path: REPRO_AP_TRACE=1 (global tracer, no explicit
    tracing() scope) + one Engine(ap_ctx=...) request ⇒ valid Perfetto
    JSON with compile/pool-wave/runtime-wavefront spans and attribution
    summing bit-exactly to the request's APStats / Table XI energy."""
    import jax
    from repro.configs import get_smoke_config
    from repro.core.energy import energy_from_stats
    from repro.launch.mesh import make_smoke_mesh
    from repro.models import model as M
    from repro.models.quant import quantize_model_params
    from repro.serve.engine import Engine, ServeCfg
    monkeypatch.setenv(trace.TRACE_ENV, "1")
    trace.reset_global_tracer()
    apc.clear_compile_caches()
    try:
        base = get_smoke_config("qwen3-0.6b")
        cfg = base.with_(n_layers=1, d_model=16, d_ff=24, n_heads=2,
                         n_kv_heads=2, head_dim=8, vocab=32,
                         ternary=base.ternary.__class__(enabled=True))
        mesh = make_smoke_mesh()
        qparams = quantize_model_params(
            M.init_params(cfg, jax.random.PRNGKey(0)))
        pool = apc.ArrayPool(n_arrays=4, rows=64, cols=64)
        ctx = apc.APServeContext(apc.Runtime(pool), x_levels=7)
        eng = Engine(cfg, qparams, mesh, ServeCfg(max_len=8), ap_ctx=ctx)
        eng.generate(np.array([[3]], dtype=np.int32), 1)
        t = trace.global_tracer()
        events = trace.validate_chrome_trace(
            json.loads(json.dumps(t.to_chrome())))
        names = {e["name"] for e in events if e["ph"] == "X"}
        assert "request" in names and "prefill" in names
        assert any(n.startswith("compile:") for n in names)
        assert any(n.startswith("wave") for n in names)
        assert any(n.startswith("wavefront") for n in names)
        tot = t.total_ap_stats(ctx.radix)
        assert tot.sets == ctx.stats.sets
        assert tot.n_compare_cycles == ctx.stats.n_compare_cycles
        assert tot.n_write_cycles == ctx.stats.n_write_cycles
        assert np.array_equal(tot.mismatch_hist, ctx.stats.mismatch_hist)
        from repro.apc.layers import N_MASKED_MAC
        assert energy_from_stats(tot, n_masked=N_MASKED_MAC).total_j == \
            energy_from_stats(ctx.stats, n_masked=N_MASKED_MAC).total_j
        rep = eng.ap_report()
        assert rep["phases"] and rep["cache"] and rep["latency"]
    finally:
        monkeypatch.delenv(trace.TRACE_ENV)
        trace.reset_global_tracer()


@pytest.mark.slow
def test_generate_latency_buckets_sum_to_request_ms():
    """ISSUE 7 satellite: prefill_ms + decode_ms + other_ms == request_ms
    (first-token sampling and AP-context setup no longer fall outside
    every bucket), and the sub-buckets partition other_ms."""
    import jax
    from repro.configs import get_smoke_config
    from repro.launch.mesh import make_smoke_mesh
    from repro.models import model as M
    from repro.models.quant import quantize_model_params
    from repro.serve.engine import Engine, ServeCfg
    base = get_smoke_config("qwen3-0.6b")
    cfg = base.with_(n_layers=1, d_model=16, d_ff=24, n_heads=2,
                     n_kv_heads=2, head_dim=8, vocab=32,
                     ternary=base.ternary.__class__(enabled=True))
    mesh = make_smoke_mesh()
    qparams = quantize_model_params(M.init_params(cfg, jax.random.PRNGKey(0)))
    pool = apc.ArrayPool(n_arrays=4, rows=64, cols=64)
    ctx = apc.APServeContext(apc.Runtime(pool), x_levels=7)
    eng = Engine(cfg, qparams, mesh, ServeCfg(max_len=8), ap_ctx=ctx)
    eng.generate(np.array([[3, 5]], dtype=np.int32), 3)
    lat = eng.last_latency
    assert lat["request_ms"] > 0
    assert abs(lat["prefill_ms"] + lat["decode_ms"] + lat["other_ms"]
               - lat["request_ms"]) <= 1e-6 * lat["request_ms"] + 1e-9
    assert abs(lat["setup_ms"] + lat["sample_ms"] + lat["finalize_ms"]
               - lat["other_ms"]) <= 1e-6 * lat["other_ms"] + 1e-9
    assert lat["n_model_steps"] == 2 + 3 - 1
    rep = eng.ap_report()
    assert rep["latency"] is lat
