"""Perf-regression sentinel over the recorded benchmark trajectories.

The repo's benchmark story lives in ``benchmarks/apc_bench.json`` — rows
recorded by :mod:`kernels_bench` / :mod:`serve_bench` the day a feature
landed.  This sentinel keeps that story honest two ways:

**Structural re-derivation** (``--smoke``, the CI gate): every recorded
column that is *schedule-static* — step counts, cycle totals,
occupancy-model makespans, admission schema — is recomputed from the
CURRENT code (compile the program again, price the graph again) and
compared to the recorded value exactly.  A code change that silently
alters cycle counts, pruning, or the occupancy model trips the
sentinel without running a single benchmark.  Wall-clock columns are only
sanity-checked (positive, p50 <= p99) because the recording host is not
this host.

**Fresh-run comparison** (``--fresh FILE``): compare a freshly produced
benchmark JSON (same schema; e.g. the output of ``kernels_bench.py`` /
``serve_bench.py --record`` pointed at a scratch file) against the
recorded baseline.  Rows are joined on each trajectory's identity columns
and timing columns must stay within a per-trajectory relative tolerance
(generous — CI hosts are noisy); structural columns must match exactly.

Exit codes: 0 all checks pass, 1 regression detected, 2 usage error.

Usage::

    PYTHONPATH=src python benchmarks/regression_sentinel.py --smoke
    PYTHONPATH=src python benchmarks/regression_sentinel.py \
        --fresh /tmp/fresh_bench.json
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import jax.numpy as jnp                                       # noqa: E402

from repro import apc                                         # noqa: E402
from repro.apc.graph import ProgramGraph, graph_makespan      # noqa: E402
from repro.core.energy import T_WRITE_NS                      # noqa: E402

DEFAULT_JSON = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "apc_bench.json")

# per-trajectory fresh-run comparison config: identity columns that join
# fresh rows to baseline rows, timing columns bounded by ``rel`` (fresh
# may be at most (1 + rel) x the recorded value), structural columns that
# must match exactly
TRAJECTORIES = {
    "results": {
        "key": ("op", "radix", "rows", "width"),
        "timing": ("replay_stats_us", "apc_stats_us", "apc_us"),
        "exact": (),
        "rel": 3.0,
    },
    "ap_matmul": {
        "key": ("m", "k", "n", "radix"),
        "timing": ("ap_us", "packed_us", "ref_us"),
        "exact": ("acc_width", "write_cycles", "compare_cycles"),
        "rel": 3.0,
    },
    "ap_pool": {
        "key": ("m", "k", "n", "n_arrays", "k_tile"),
        "timing": ("us",),
        "exact": ("acc_width", "n_tiles", "cols_budget", "n_blocks",
                  "waves", "write_cycles", "compare_cycles",
                  "wall_write_cycles", "wall_compare_cycles"),
        "rel": 3.0,
    },
    "ap_runtime": {
        "key": ("g_programs", "m", "k", "n", "n_arrays", "n_devices"),
        "timing": ("us_runtime", "us_sequential"),
        "exact": ("acc_width", "n_tiles", "cols_budget", "n_nodes",
                  "makespan_cycles", "sequential_cycles"),
        "rel": 3.0,
    },
    "ap_sparse": {
        "key": ("m", "k", "n", "zero_frac"),
        "timing": ("us_streaming", "us_resident",
                   "encode_us_streaming", "encode_us_resident"),
        "exact": ("acc_width", "cols_budget", "dense_write_cycles",
                  "dense_compare_cycles"),
        "rel": 3.0,
    },
    "ap_serve": {
        "key": ("offered_rps", "n_requests", "max_inflight"),
        "timing": ("p50_ms", "p99_ms", "mean_ms", "wall_s"),
        "exact": ("s_prompt", "n_new"),
        "rel": 3.0,
    },
    "ap_faults": {
        "key": ("flip_rate", "n_dead"),
        "timing": ("p50_ms", "p99_ms", "wall_s"),
        "exact": ("n_arrays", "n_requests", "n_new", "seed"),
        "rel": 3.0,
    },
}


def _mac_setup(radix: int, k: int, k_tile: int, max_abs: int = 3):
    """The shared (width, cols, tiled) derivation of the MAC benches."""
    width = apc.mac_acc_width(radix, k, max_abs)
    cols = apc.mac_layout(min(k_tile, k), width)["n_cols"]
    tiled = apc.compile_mac_tiled(radix, k, width, k_tile, max_cols=cols)
    return width, cols, tiled


# ---------------------------------------------------------------------------
# Structural re-derivation per trajectory
# ---------------------------------------------------------------------------

def check_ap_matmul(rows: list[dict]) -> list[str]:
    from repro.kernels.ternary_matmul.ap import ap_matmul_cycle_counts
    problems = []
    for r in rows:
        width = apc.mac_acc_width(r["radix"], r["k"], 3)
        cyc = ap_matmul_cycle_counts(r["radix"], r["k"], width)
        got = {"acc_width": width,
               "write_cycles": cyc["write_cycles"],
               "compare_cycles": cyc["compare_cycles"],
               "ap_delay_ns": cyc["write_cycles"] * T_WRITE_NS
               + cyc["compare_cycles"] * 2.0}
        for col, val in got.items():
            if r.get(col) != val:
                problems.append(
                    f"ap_matmul {r['m']}x{r['k']}x{r['n']}: {col} "
                    f"recorded {r.get(col)} != derived {val}")
        if not (0 < r["energy_total_j"]
                and r["energy_total_j"] == r["energy_write_j"]
                + r["energy_compare_j"]):
            problems.append(
                f"ap_matmul {r['m']}x{r['k']}x{r['n']}: energy columns "
                f"inconsistent (total != write + compare)")
    return problems


def check_ap_pool(rows: list[dict]) -> list[str]:
    problems = []
    for r in rows:
        width, cols, tiled = _mac_setup(r["radix"], r["k"], r["k_tile"])
        pool = apc.ArrayPool(n_arrays=r["n_arrays"], rows=r["pool_rows"],
                             cols=cols)
        wall = pool.wall_cycles(r["m"] * r["n"], tiled.n_compare_cycles,
                                tiled.n_write_cycles)
        got = {"acc_width": width, "cols_budget": cols,
               "n_tiles": len(tiled.tiles),
               "n_blocks": pool.n_blocks(r["m"] * r["n"]),
               "write_cycles": tiled.n_write_cycles,
               "compare_cycles": tiled.n_compare_cycles,
               "waves": wall["waves"],
               "wall_write_cycles": wall["write_cycles"],
               "wall_compare_cycles": wall["compare_cycles"]}
        for col, val in got.items():
            if r.get(col) != val:
                problems.append(
                    f"ap_pool a{r['n_arrays']}kt{r['k_tile']}: {col} "
                    f"recorded {r.get(col)} != derived {val}")
    return problems


def check_ap_runtime(rows: list[dict]) -> list[str]:
    problems = []
    for r in rows:
        width, cols, tiled = _mac_setup(r["radix"], r["k"], r["k_tile"])
        rows_mac = r["m"] * r["n"]
        x = jnp.zeros((rows_mac, r["k"]), jnp.int32)
        w = jnp.zeros((rows_mac, r["k"]), jnp.int8)
        g = ProgramGraph()
        for _ in range(r["g_programs"]):
            g.add_mac_tiled(x, w, tiled)
        rep = graph_makespan(g, n_arrays=r["n_arrays"],
                             rows_per_array=r["pool_rows"],
                             n_devices=r["n_devices"])
        got = {"acc_width": width, "cols_budget": cols,
               "n_tiles": len(tiled.tiles), "n_nodes": len(g),
               "makespan_cycles": rep["makespan_cycles"],
               "sequential_cycles": rep["sequential_cycles"],
               "makespan_ns": round(rep["makespan_ns"]),
               "sequential_ns": round(rep["sequential_ns"])}
        for col, val in got.items():
            if r.get(col) != val:
                problems.append(
                    f"ap_runtime d{r['n_devices']}a{r['n_arrays']}: {col} "
                    f"recorded {r.get(col)} != derived {val}")
    return problems


def check_ap_sparse(rows: list[dict]) -> list[str]:
    """Dense baseline re-derived exactly; the pruned columns (which depend
    on the bench's random zero pattern) are held to invariants instead:
    pruning is real (reduction tracks zero_frac) and never corrupts the
    pass accounting."""
    problems = []
    for r in rows:
        width, cols, dense = _mac_setup(r["radix"], r["k"], r["k_tile"])
        got = {"acc_width": width, "cols_budget": cols,
               "dense_write_cycles": dense.n_write_cycles,
               "dense_compare_cycles": dense.n_compare_cycles}
        tag = f"ap_sparse zf{r['zero_frac']}"
        for col, val in got.items():
            if r.get(col) != val:
                problems.append(f"{tag}: {col} recorded {r.get(col)} "
                                f"!= derived {val}")
        if r["write_cycles"] > r["dense_write_cycles"]:
            problems.append(f"{tag}: pruned write_cycles exceed dense")
        want_red = round(1 - r["write_cycles"] / r["dense_write_cycles"], 4)
        if r["write_cycle_reduction"] != want_red:
            problems.append(f"{tag}: write_cycle_reduction "
                            f"{r['write_cycle_reduction']} != {want_red}")
        if r["zero_frac"] > 0 and \
                r["write_cycle_reduction"] < 0.9 * r["zero_frac"]:
            problems.append(
                f"{tag}: reduction {r['write_cycle_reduction']} below "
                f"0.9 * zero_frac — pruning regressed")
    return problems


def check_ap_serve(rows: list[dict]) -> list[str]:
    """Admission/latency schema + internal consistency (host-independent)."""
    required = ("offered_rps", "achieved_rps", "p50_ms", "p99_ms",
                "mean_ms", "n_requests", "s_prompt", "n_new",
                "max_inflight", "n_waves", "queued", "rejected",
                "max_queue_depth", "wall_s")
    problems = []
    for r in rows:
        tag = f"ap_serve rps{r.get('offered_rps')}"
        missing = [c for c in required if c not in r]
        if missing:
            problems.append(f"{tag}: missing columns {missing}")
            continue
        if not (0 < r["p50_ms"] <= r["p99_ms"]):
            problems.append(f"{tag}: p50/p99 ordering broken")
        if r["achieved_rps"] <= 0 or r["n_waves"] <= 0:
            problems.append(f"{tag}: degenerate throughput row")
        if r["queued"] > r["n_requests"] or r["rejected"] > r["n_requests"]:
            problems.append(f"{tag}: admission counters exceed n_requests")
        if r["max_queue_depth"] > r["n_requests"]:
            problems.append(f"{tag}: max_queue_depth exceeds n_requests")
    return problems


def check_ap_faults(rows: list[dict]) -> list[str]:
    """Fault-sweep schema + recovery invariants (host-independent): the
    zero-rate point is clean by seeding, detection work scales with the
    injected rate, and the surviving-bank accounting balances."""
    required = ("flip_rate", "n_dead", "seed", "n_arrays", "n_requests",
                "n_new", "achieved_rps", "p50_ms", "p99_ms", "detected",
                "retries", "checksum_runs", "retired", "surviving_arrays",
                "wall_s")
    problems = []
    for r in rows:
        tag = f"ap_faults flip{r.get('flip_rate')}d{r.get('n_dead')}"
        missing = [c for c in required if c not in r]
        if missing:
            problems.append(f"{tag}: missing columns {missing}")
            continue
        if not (0 < r["p50_ms"] <= r["p99_ms"]) or r["achieved_rps"] <= 0:
            problems.append(f"{tag}: degenerate latency/throughput row")
        if r["flip_rate"] == 0 and (r["detected"] or r["retries"]):
            problems.append(
                f"{tag}: zero-rate point recorded fault activity "
                f"(detected={r['detected']}, retries={r['retries']})")
        if r["checksum_runs"] <= 0:
            problems.append(f"{tag}: checksum verify path never ran")
        if r["retries"] > r["detected"]:
            problems.append(f"{tag}: more retries than detections")
        want_surv = r["n_arrays"] - r["n_dead"] - r["retired"]
        if r["surviving_arrays"] != want_surv:
            problems.append(
                f"{tag}: surviving_arrays {r['surviving_arrays']} != "
                f"n_arrays - n_dead - retired = {want_surv}")
    if rows:
        if not any(r["flip_rate"] == 0 for r in rows):
            problems.append("ap_faults: no zero-rate baseline point")
        top = max(rows, key=lambda r: r["flip_rate"])
        if top["flip_rate"] > 0 and top["detected"] <= 0:
            problems.append(
                "ap_faults: max-rate point detected nothing — the "
                "injector or the checksum path is dead")
    return problems


def check_trace_overhead(row: dict) -> list[str]:
    problems = []
    compiled = apc.compile_named(row["op"], row["radix"], row["width"])
    if row["n_steps"] != compiled.n_steps:
        problems.append(f"trace_overhead: n_steps recorded "
                        f"{row['n_steps']} != derived {compiled.n_steps}")
    for col in ("untraced_us", "traced_us", "noop_span_ns"):
        if row.get(col, 0) <= 0:
            problems.append(f"trace_overhead: {col} not positive")
    return problems


def check_results(rows: list[dict]) -> list[str]:
    problems = []
    for r in rows:
        tag = f"apc {r['rows']}x{r['width']}"
        for col in ("replay_stats_us", "apc_stats_us", "apc_us"):
            if r.get(col, 0) <= 0:
                problems.append(f"{tag}: {col} not positive")
        # recorded speedup was computed before the us columns were rounded
        want = r["replay_stats_us"] / r["apc_stats_us"]
        if abs(r["speedup_stats_x"] - want) > 0.01 * want:
            problems.append(f"{tag}: speedup_stats_x inconsistent "
                            f"({r['speedup_stats_x']} != ~{want:.2f})")
    return problems


STRUCTURAL_CHECKS = {
    "results": check_results,
    "ap_matmul": check_ap_matmul,
    "ap_pool": check_ap_pool,
    "ap_runtime": check_ap_runtime,
    "ap_sparse": check_ap_sparse,
    "ap_serve": check_ap_serve,
    "ap_faults": check_ap_faults,
    "trace_overhead": check_trace_overhead,
}


def run_structural(doc: dict) -> list[str]:
    problems = []
    for name, fn in STRUCTURAL_CHECKS.items():
        if name not in doc:
            problems.append(f"{name}: trajectory missing from baseline")
            continue
        problems.extend(fn(doc[name]))
    return problems


# ---------------------------------------------------------------------------
# Fresh-run comparison
# ---------------------------------------------------------------------------

def compare_fresh(baseline: dict, fresh: dict) -> list[str]:
    problems = []
    for name, cfg in TRAJECTORIES.items():
        if name not in fresh:
            continue                     # partial fresh runs are fine
        if name not in baseline:
            problems.append(f"{name}: in fresh doc but not in baseline")
            continue
        base_rows = {tuple(r.get(c) for c in cfg["key"]): r
                     for r in baseline[name]}
        for fr in fresh[name]:
            key = tuple(fr.get(c) for c in cfg["key"])
            br = base_rows.get(key)
            if br is None:
                continue                 # new sweep point: nothing to hold
            tag = f"{name} {dict(zip(cfg['key'], key))}"
            for col in cfg["exact"]:
                if fr.get(col) != br.get(col):
                    problems.append(
                        f"{tag}: structural column {col} changed "
                        f"{br.get(col)} -> {fr.get(col)}")
            for col in cfg["timing"]:
                b, f = br.get(col), fr.get(col)
                if not b or f is None:
                    continue
                if f > b * (1.0 + cfg["rel"]):
                    problems.append(
                        f"{tag}: {col} regressed {b} -> {f} "
                        f"(> {1.0 + cfg['rel']:.1f}x tolerance)")
    return problems


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--json", default=DEFAULT_JSON,
                   help="recorded baseline (apc_bench.json)")
    p.add_argument("--smoke", action="store_true",
                   help="structural re-derivation only (the CI gate)")
    p.add_argument("--fresh", default=None,
                   help="fresh benchmark JSON to compare against baseline")
    args = p.parse_args(argv)
    if not args.smoke and not args.fresh:
        print("regression_sentinel: pass --smoke and/or --fresh FILE",
              file=sys.stderr)
        return 2
    try:
        with open(args.json) as f:
            baseline = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        print(f"regression_sentinel: cannot read baseline {args.json}: {e}",
              file=sys.stderr)
        return 2

    problems = run_structural(baseline)
    n_struct = len(problems)
    print(f"structural re-derivation: "
          f"{len(STRUCTURAL_CHECKS)} trajectories, "
          f"{n_struct} problem(s)")

    if args.fresh:
        try:
            with open(args.fresh) as f:
                fresh = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            print(f"regression_sentinel: cannot read fresh doc "
                  f"{args.fresh}: {e}", file=sys.stderr)
            return 2
        fresh_problems = compare_fresh(baseline, fresh)
        print(f"fresh comparison: {len(fresh_problems)} problem(s)")
        problems.extend(fresh_problems)

    for msg in problems:
        print(f"  REGRESSION: {msg}")
    if problems:
        print(f"regression_sentinel: FAIL ({len(problems)} problem(s))")
        return 1
    print("regression_sentinel: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
