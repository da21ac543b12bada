"""Device idle time by program span, on hand-made spans and on a trace of
the program recorded here."""
import pytest

import span_reduce as sr
import trace_reduce as tr

MS = 1_000_000


def _case():
    """One device busy over [0, 2), [5, 6) and [9, 10) ms of a 12 ms
    window; program spans on two host threads, 0 and 1."""
    devices = {"/device:TPU:0": [("k", 0, 2 * MS), ("k", 5 * MS, 6 * MS),
                                 ("k", 9 * MS, 10 * MS)]}
    spans = [("ap.serve.wave", MS, 11 * MS, 0),          # waits
             ("ap.runtime.run_graph", 2 * MS, 8 * MS, 1),
             ("ap.pool.launch", 3 * MS, 4 * MS, 1),
             ("ap.stats.sync", 6 * MS, 9 * MS, 1)]        # waits
    return devices, spans, (0, 12 * MS)


def test_idle_goes_to_the_innermost_working_span():
    devices, spans, window = _case()
    u = sr.first_busy_union(devices, window)
    out = sr.span_times(u, spans, window)
    # gaps [2,5), [6,9), [10,12).  [2,3) and [4,5): the graph run, where
    # thread 1 works and thread 0's wave yields; [3,4): the launch,
    # innermost on thread 1.  [6,9): thread 1's innermost is the sync; both
    # threads wait there, and the shorter wait wins.  [10,11): the wave
    # alone.  [11,12): no program span.
    assert out["idle_s"] == pytest.approx({
        "ap.runtime.run_graph": 0.002, "ap.pool.launch": 0.001,
        "ap.stats.sync": 0.003, "ap.serve.wave": 0.001, "": 0.001})
    assert out["host_s"] == {
        "ap.serve.wave": [1, pytest.approx(0.010)],
        "ap.runtime.run_graph": [1, pytest.approx(0.006)],
        "ap.pool.launch": [1, pytest.approx(0.001)],
        "ap.stats.sync": [1, pytest.approx(0.003)]}
    # the idle shares partition device_idle over the same window
    ref = tr.reduce_events(devices, [("bench.window", *window)], window)
    assert sum(out["idle_s"].values()) == pytest.approx(
        ref["window_s"] - ref["busy_s"])
    got = sr.metrics(out, ref["window_s"])
    assert sum(got[k] for k in sr.IDLE_LAYERS) == pytest.approx(
        100.0 * (1 - ref["busy_s"] / ref["window_s"]))
    assert got["idle_dispatch"] == pytest.approx(100 * 3 / 12)
    assert got["idle_sync"] == pytest.approx(100 * 3 / 12)
    assert got["launch_host_us"] == pytest.approx(1000.0)


def test_wait_yields_to_work_on_another_thread_not_to_its_parent():
    devices = {"/device:TPU:0": [("k", 9 * MS, 10 * MS)]}
    spans = [("ap.model.step", 0, 9 * MS, 0),
             ("ap.serve.rendezvous", MS, 8 * MS, 0),     # waits, in a step
             ("ap.model.step", 2 * MS, 9 * MS, 1),
             ("ap.model.graph_build", 4 * MS, 5 * MS, 1)]
    window = (0, 10 * MS)
    out = sr.span_times(sr.first_busy_union(devices, window), spans, window)
    # [0,1): thread 0's step.  [1,2): its rendezvous, with no other work.
    # [2,8) but [4,5): thread 1's step works; [4,5): its graph build.
    # [8,9): both steps; the shorter.  [9,10): busy.
    assert out["idle_s"] == pytest.approx({
        "ap.model.step": 0.001 + 0.005 + 0.001,
        "ap.serve.rendezvous": 0.001, "ap.model.graph_build": 0.001,
        "": 0.0})


def test_spans_opened_at_once_nest_by_length():
    spans = [("ap.pool.run", 0, 4 * MS, 0), ("ap.pool.launch", 0, MS, 0)]
    out = sr.span_times(None, spans, (0, 4 * MS))
    assert out["idle_s"] == pytest.approx({"ap.pool.launch": 0.001,
                                           "ap.pool.run": 0.003, "": 0.0})


def test_no_program_span_reads_nothing():
    out = sr.span_times(None, [("bench.run_pooled", 0, MS, 0)], (0, MS))
    assert out == {"host_s": {}, "idle_s": {"": pytest.approx(0.001)}}
    assert sr.metrics(out, 0.001) == {}


def test_recorded_trace_of_a_pooled_run(tmp_path):
    import jax
    import jax.numpy as jnp
    from repro import apc
    from repro.apc.pool import ArrayPool, run_pooled
    from repro.core.ap import APStats
    prog = apc.compile_named("add", 3, 4)
    pool = ArrayPool(n_arrays=2, rows=16, cols=16)
    x = jnp.zeros((40, prog.min_cols), jnp.int8)
    run_pooled(x, prog, pool, stats=APStats(radix=3))      # compile
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        run_pooled(x, prog, pool, stats=APStats(radix=3))
    jax.profiler.stop_trace()
    out = sr.reduce_spans(str(tmp_path))
    assert out["host_s"]["ap.pool.launch"][0] == 3
    assert out["host_s"]["ap.pool.run"][0] == 1
    assert out["host_s"]["ap.stats.sync"][0] == 1
    # the CPU has no device plane: the whole window is idle
    window_s = tr.reduce_trace(str(tmp_path))["window_s"]
    assert sum(out["idle_s"].values()) == pytest.approx(window_s)
    assert sr.metrics(out, window_s)["launch_host_us"] > 0


def test_span_report_of_a_small_vec_run():
    import span_report
    from test_checks import VEC
    line = span_report.report("tap-add-r3w20.short", 3, 1.0,
                              require_chip=False, **VEC)
    assert line["correct"], line["checks"]
    # the end-to-end metrics over the traced window, beside the per-layer
    assert {"vec_rows_per_s", "device_idle.vec_short"} <= set(line["metrics"])
    got = line["spans"]["metrics"]
    assert set(got) == set(sr.IDLE_LAYERS) | {"launch_host_us"}
    assert sum(got[k] for k in sr.IDLE_LAYERS) == pytest.approx(
        line["metrics"]["device_idle.vec_short"]["value"])
    assert got["idle_batcher"] == got["idle_model"] == 0.0
    assert line["spans"]["host_s"]["ap.stats.sync"][0] > 0
