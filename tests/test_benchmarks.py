"""Benchmark modules reproduce the paper's headline numbers (small n_rows
for CI speed; benchmarks.run uses the full sizes)."""
import sys

import numpy as np
import pytest

sys.path.insert(0, ".")


def test_table_xi_reductions():
    from benchmarks.table_xi import derived, run
    rows = [r for r in run(n_rows=512)]
    d = derived(rows)
    assert d["energy_reduction_pct"] == pytest.approx(12.25, abs=1.5)
    assert d["setreset_reduction_pct"] == pytest.approx(12.6, abs=1.5)
    assert d["area_reduction_pct"] == pytest.approx(6.2, abs=1.0)


def test_fig8_cla_saving():
    from benchmarks.fig8 import run
    rows, tap_per_add = run(n_probe_rows=512)
    saving = 1 - rows[-1]["tap_J"] / rows[-1]["cla_J"]
    assert saving == pytest.approx(0.5264, abs=0.02)
    # linear in rows
    assert rows[-1]["tap_J"] / rows[0]["tap_J"] == pytest.approx(
        rows[-1]["rows"] / rows[0]["rows"], rel=1e-6)


def test_fig9_ratios():
    from benchmarks.fig9 import run
    table, d = run()
    assert d["tap_nb"] / d["tap_bl"] == pytest.approx(1.4, abs=0.01)
    assert d["tap_bl"] / d["binary_32b"] == pytest.approx(2.34, abs=0.02)
    assert d["tap_best"] < d["tap_bl"]            # beyond-paper schedule
    cla512 = [r["cla_ns"] for r in table if r["rows"] == 512][0]
    assert cla512 / d["tap_nb"] == pytest.approx(6.8, abs=0.1)
    assert cla512 / d["tap_bl"] == pytest.approx(9.5, abs=0.1)


def test_fig6_7_trends():
    from benchmarks.fig6_7 import run
    sw = run()
    # best DR at lowest R_L / highest alpha; energies ordered by mismatches
    assert sw["dr"][0, -1] == sw["dr"].max()
    assert (np.diff(sw["energy"][0, -1]) > 0).all()


def test_bench_ap_pool_smoke_schema():
    """CI smoke: the ap_pool trajectory rows keep the schema the JSON
    consumers expect, at toy sizes (one tiled + one untiled config)."""
    from benchmarks.kernels_bench import bench_ap_pool
    rows = bench_ap_pool(m=2, k=12, n=2, pool_rows=4,
                         n_arrays_list=(1, 2), k_tile_list=(4,),
                         n_timing=1)
    assert len(rows) == 2
    keys = {"bench", "m", "k", "n", "radix", "acc_width", "k_tile",
            "n_tiles", "cols_budget", "pool_rows", "n_arrays", "n_blocks",
            "us", "write_cycles", "compare_cycles", "waves",
            "wall_write_cycles", "wall_compare_cycles"}
    for r in rows:
        assert keys <= set(r)
        assert r["bench"] == "ap_pool" and r["n_tiles"] >= 2
    # schedule totals are n_arrays-independent; pipelined waves shrink
    assert rows[0]["write_cycles"] == rows[1]["write_cycles"]
    assert rows[0]["waves"] >= rows[1]["waves"]


def test_bench_ap_runtime_smoke_schema():
    """CI smoke: the ap_runtime trajectory rows keep their schema at toy
    sizes, makespan <= sequential on every row, and >1 array pipelines
    strictly better than the naive drains."""
    from benchmarks.kernels_bench import bench_ap_runtime
    rows = bench_ap_runtime(g_programs=2, m=2, k=12, n=2, pool_rows=4,
                            k_tile=4, n_arrays_list=(1, 2),
                            n_devices_list=(1,), n_timing=1)
    assert len(rows) == 2
    keys = {"bench", "g_programs", "m", "k", "n", "radix", "acc_width",
            "k_tile", "n_tiles", "cols_budget", "pool_rows", "n_arrays",
            "n_devices", "n_arrays_total", "n_nodes", "us_runtime",
            "us_sequential", "makespan_cycles", "sequential_cycles",
            "makespan_ns", "sequential_ns", "pipeline_speedup_x",
            "write_cycles", "compare_cycles"}
    for r in rows:
        assert keys <= set(r)
        assert r["bench"] == "ap_runtime" and r["n_tiles"] >= 2
        assert r["makespan_cycles"] <= r["sequential_cycles"]
    # schedule totals are geometry-independent; >1 array pipelines strictly
    assert rows[0]["write_cycles"] == rows[1]["write_cycles"]
    assert rows[1]["makespan_cycles"] < rows[1]["sequential_cycles"]


def test_apc_bench_json_recorded_ap_runtime_rows():
    """The RECORDED benchmarks/apc_bench.json must carry the ap_runtime
    trajectory with the makespan <= sequential invariant intact."""
    import json
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "apc_bench.json")
    with open(path) as f:
        data = json.load(f)
    rows = data.get("ap_runtime", [])
    assert rows, "apc_bench.json is missing the ap_runtime trajectory"
    for r in rows:
        assert r["makespan_cycles"] <= r["sequential_cycles"]
        assert r["n_arrays_total"] == r["n_arrays"] * r["n_devices"]
        if r["n_arrays_total"] > 1:
            assert r["makespan_cycles"] < r["sequential_cycles"]


def test_bench_ap_sparse_smoke_schema():
    """CI smoke: the ap_sparse trajectory rows keep their schema at toy
    sizes; streaming/resident bit-equality is asserted inside the bench,
    and pruned cycles track the zero fraction."""
    from benchmarks.kernels_bench import bench_ap_sparse
    rows = bench_ap_sparse(m=2, k=8, n=2, k_tile=4, pool_rows=4,
                           zero_fracs=(0.0, 0.5), n_timing=1)
    assert len(rows) == 2
    keys = {"bench", "m", "k", "n", "radix", "acc_width", "k_tile",
            "cols_budget", "n_arrays", "zero_frac", "n_zero_k",
            "emitted_passes", "pruned_passes", "write_cycles",
            "compare_cycles", "dense_write_cycles", "dense_compare_cycles",
            "write_cycle_reduction", "us_streaming", "us_resident",
            "encode_us_streaming", "encode_us_resident", "resident_hits"}
    for r in rows:
        assert keys <= set(r)
        assert r["bench"] == "ap_sparse"
        assert r["write_cycles"] <= r["dense_write_cycles"]
        assert r["write_cycle_reduction"] >= 0.9 * r["zero_frac"]
    dense, half = rows
    assert dense["zero_frac"] == 0.0 and dense["pruned_passes"] == 0
    assert dense["write_cycles"] == dense["dense_write_cycles"]
    assert half["pruned_passes"] == 2 * half["n_zero_k"] > 0
    assert half["write_cycles"] < dense["write_cycles"]


def test_apc_bench_json_recorded_ap_sparse_rows():
    """The RECORDED benchmarks/apc_bench.json must carry the ap_sparse
    trajectory: cycle reduction tracking the zero fraction (>= 0.9 * s on
    every row) across both dataflows."""
    import json
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "apc_bench.json")
    with open(path) as f:
        data = json.load(f)
    rows = data.get("ap_sparse", [])
    assert rows, "apc_bench.json is missing the ap_sparse trajectory"
    assert len(rows) >= 3              # a curve, not a point
    fracs = [r["zero_frac"] for r in rows]
    assert fracs == sorted(fracs) and fracs[0] == 0.0 and fracs[-1] >= 0.9
    for r in rows:
        assert r["bench"] == "ap_sparse"
        assert r["write_cycles"] <= r["dense_write_cycles"]
        assert r["compare_cycles"] <= r["dense_compare_cycles"]
        assert r["write_cycle_reduction"] >= 0.9 * r["zero_frac"]
        assert r["us_streaming"] > 0 and r["us_resident"] > 0
        assert r["encode_us_streaming"] > 0 and r["encode_us_resident"] > 0
        assert r["pruned_passes"] == 2 * r["n_zero_k"]


@pytest.mark.slow
def test_serve_bench_load_point_schema():
    """One serve_bench load point end-to-end: the ap_serve row carries the
    serving-curve schema and sane values."""
    import os
    bench_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    sys.path.insert(0, bench_dir)
    try:
        from serve_bench import run_load_point
    finally:
        sys.path.remove(bench_dir)
    row = run_load_point(8.0, 4, max_inflight=4, s_prompt=2, n_new=2)
    keys = {"bench", "offered_rps", "achieved_rps", "p50_ms", "p99_ms",
            "mean_ms", "n_requests", "max_inflight", "n_waves", "wall_s",
            "queued", "rejected", "max_queue_depth"}
    assert keys <= set(row)
    assert row["bench"] == "ap_serve"
    assert row["achieved_rps"] > 0
    assert 0 < row["p50_ms"] <= row["p99_ms"]
    assert row["n_waves"] >= row["s_prompt"] + row["n_new"] - 1
    # admission accounting: every request either ran straight through or
    # waited; nothing exceeds the offered request count
    assert 0 <= row["queued"] <= row["n_requests"]
    assert row["rejected"] == 0            # block policy: no sheds
    assert 0 <= row["max_queue_depth"] <= row["n_requests"]


def test_apc_bench_json_recorded_ap_serve_rows():
    """The RECORDED benchmarks/apc_bench.json must carry the ap_serve
    serving trajectory (requests/sec + p50/p99 vs offered load)."""
    import json
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "apc_bench.json")
    with open(path) as f:
        data = json.load(f)
    rows = data.get("ap_serve", [])
    assert rows, "apc_bench.json is missing the ap_serve trajectory"
    assert len(rows) >= 2              # a curve, not a point
    offered = [r["offered_rps"] for r in rows]
    assert offered == sorted(offered)
    for r in rows:
        assert r["bench"] == "ap_serve"
        assert r["achieved_rps"] > 0
        assert 0 < r["p50_ms"] <= r["p99_ms"]
        # open loop: achieved throughput cannot exceed what was offered
        # by more than rounding
        assert r["achieved_rps"] <= r["offered_rps"] * 1.05 + 0.5
        # admission columns (ISSUE 9): recorded rows carry the queue story
        assert 0 <= r["queued"] <= r["n_requests"]
        assert 0 <= r["rejected"] <= r["n_requests"]
        assert 0 <= r["max_queue_depth"] <= r["n_requests"]
    # queue pressure grows with offered load along the recorded curve
    assert rows[-1]["queued"] >= rows[0]["queued"]


@pytest.mark.slow
def test_bench_ap_faults_point_schema():
    """One faults_bench sweep point end-to-end: the ap_faults row carries
    the fault-recovery schema and the accounting balances."""
    import os
    bench_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    sys.path.insert(0, bench_dir)
    try:
        from faults_bench import run_fault_point
    finally:
        sys.path.remove(bench_dir)
    row = run_fault_point(1e-3, (), n_requests=2, n_new=2, s_prompt=2)
    keys = {"bench", "flip_rate", "n_dead", "seed", "n_arrays",
            "n_requests", "n_new", "achieved_rps", "p50_ms", "p99_ms",
            "detected", "retries", "checksum_runs", "retired",
            "surviving_arrays", "wall_s"}
    assert keys <= set(row)
    assert row["bench"] == "ap_faults"
    assert row["achieved_rps"] > 0
    assert 0 < row["p50_ms"] <= row["p99_ms"]
    assert row["checksum_runs"] > 0        # verify path really ran
    assert row["retries"] <= row["detected"]
    assert row["surviving_arrays"] == \
        row["n_arrays"] - row["n_dead"] - row["retired"]


def test_apc_bench_json_recorded_ap_faults_rows():
    """The RECORDED benchmarks/apc_bench.json must carry the ap_faults
    fault-tolerance trajectory (throughput/recovery cost vs fault rate,
    ending in the degraded-bank point)."""
    import json
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "apc_bench.json")
    with open(path) as f:
        data = json.load(f)
    rows = data.get("ap_faults", [])
    assert rows, "apc_bench.json is missing the ap_faults trajectory"
    assert len(rows) >= 3                  # a sweep, not a point
    for r in rows:
        assert r["bench"] == "ap_faults"
        assert r["achieved_rps"] > 0
        assert 0 < r["p50_ms"] <= r["p99_ms"]
        assert r["checksum_runs"] > 0
        assert r["surviving_arrays"] == \
            r["n_arrays"] - r["n_dead"] - r["retired"]
    # the sweep spans pristine -> faulty -> degraded bank
    assert any(r["flip_rate"] == 0 and r["detected"] == 0 for r in rows)
    assert any(r["flip_rate"] > 0 and r["detected"] > 0 for r in rows)
    assert any(r["n_dead"] > 0 and r["surviving_arrays"] < r["n_arrays"]
               for r in rows)


# ---------------------------------------------------------------------------
# perf-regression sentinel
# ---------------------------------------------------------------------------

def _sentinel():
    import os
    bench_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    sys.path.insert(0, bench_dir)
    try:
        import regression_sentinel
    finally:
        sys.path.remove(bench_dir)
    return regression_sentinel


def test_regression_sentinel_smoke_passes_on_recorded():
    """The recorded apc_bench.json re-derives clean from current code."""
    assert _sentinel().main(["--smoke"]) == 0


def test_regression_sentinel_flags_degraded_fresh_rows(tmp_path, capsys):
    """A synthetically slowed timing column and a structural drift both
    trip the sentinel (exit 1, named in the output)."""
    import json
    sent = _sentinel()
    with open(sent.DEFAULT_JSON) as f:
        doc = json.load(f)
    ok = tmp_path / "fresh_ok.json"
    ok.write_text(json.dumps(doc))
    assert sent.main(["--smoke", "--fresh", str(ok)]) == 0

    bad = json.loads(json.dumps(doc))
    bad["ap_matmul"][0]["ap_us"] *= 100          # timing regression
    bad["ap_runtime"][0]["makespan_cycles"] += 1  # occupancy model drift
    path = tmp_path / "fresh_bad.json"
    path.write_text(json.dumps(bad))
    assert sent.main(["--fresh", str(path)]) == 1
    out = capsys.readouterr().out
    assert "ap_us regressed" in out
    assert "makespan_cycles changed" in out


def test_regression_sentinel_usage_errors(tmp_path):
    sent = _sentinel()
    assert sent.main([]) == 2                    # no mode selected
    assert sent.main(["--fresh", str(tmp_path / "missing.json")]) == 2
    broken = tmp_path / "broken.json"
    broken.write_text("{not json")
    assert sent.main(["--smoke", "--json", str(broken)]) == 2


def test_regression_sentinel_smoke_catches_structural_baseline_drift(
        tmp_path):
    """If someone edits a recorded schedule-static column, --smoke fails:
    the sentinel re-derives it from current code."""
    import json
    sent = _sentinel()
    with open(sent.DEFAULT_JSON) as f:
        doc = json.load(f)
    doc["ap_pool"][0]["wall_write_cycles"] += 1
    doc["ap_matmul"][0]["write_cycles"] += 1
    # fault-trajectory invariant: surviving-bank accounting must balance
    doc["ap_faults"][-1]["surviving_arrays"] += 1
    path = tmp_path / "tampered.json"
    path.write_text(json.dumps(doc))
    assert sent.main(["--smoke", "--json", str(path)]) == 1
