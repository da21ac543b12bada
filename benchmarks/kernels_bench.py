"""Kernel-level benchmarks (CPU host: wall time from the jnp reference paths,
structural HBM-traffic/bytes arithmetic for the TPU roofline story).

1. tap_pass fusion: HBM bytes naive per-pass replay vs one fused VMEM pass
   (the paper's in-memory property on TPU), + wall time of the jnp path.
2. ternary_matmul: weight bytes bf16 vs 2-bit packed (8x) and wall time of
   the fake-quant vs dense matmul on CPU.
3. apc: whole-program compiler (fused pallas executor, traced stats) vs the
   interpreted pass-by-pass apply_lut replay, JSON-emitted so future PRs
   have a perf trajectory (benchmarks/apc_bench.json).
4. ap matmul: the MAC-program backend (impl="ap") vs the packed Pallas
   kernel vs the jnp ref across (M, K) — wall time plus the AP cost model
   (schedule-static compare/write cycles and Table XI energy from the
   functional-simulator counters), appended to the same JSON trajectory.
5. ap pool: the array-pool pipelined executor with K-tiled MAC programs —
   wall-clock and (pipelined) write-cycle scaling vs n_arrays and k_tile
   under a fixed column budget, the bank-level parallelism story
   ("ap_pool" trajectory in apc_bench.json).
6. ap runtime: the program-graph scheduler — G independent tiled-MAC
   matmuls as ONE ProgramGraph vs naive sequential pool drains, across
   (n_devices, n_arrays): wall clock plus the occupancy model's graph
   makespan vs sequential wall-cycle sum ("ap_runtime" trajectory).
   n_devices > 1 rows appear when the process sees multiple devices
   (XLA_FLAGS=--xla_force_host_platform_device_count=4).
7. ap sparse: sparsity-compressed MAC programs + the weight-stationary
   resident bank ("ap_sparse" trajectory) — schedule cycles and wall
   clock vs weight zero-fraction (0.0 -> 0.9), streaming vs resident
   dataflow, with the host-side row-encode cost of each.
"""
from __future__ import annotations

import json
import os
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro import apc
from repro.core import ap, truth_tables as tt
from repro.core.nonblocked import build_lut_nonblocked
from repro.kernels.tap_pass.ops import hbm_traffic_model
from repro.kernels.tap_pass.ref import apply_schedule, ripple_add_schedule
from repro.kernels.ternary_matmul.ops import quantize_and_pack
from repro.kernels.ternary_matmul.ref import ternary_matmul_ref
from repro.launch.compile_cache import enable_compile_cache


def _time(fn, *args, n=5) -> float:
    fn(*args)                      # compile
    t0 = time.perf_counter()
    for _ in range(n):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / n * 1e6


def bench_tap(rows: int = 8192, width: int = 20):
    lut = build_lut_nonblocked(tt.full_adder(3))
    rng = np.random.default_rng(0)
    a = rng.integers(0, 3 ** width, rows)
    b = rng.integers(0, 3 ** width, rows)
    arr = jnp.asarray(ap.encode_operands(a, b, 3, width))
    sched = ripple_add_schedule(lut, width, 2 * width)
    f = jax.jit(lambda x: apply_schedule(x, sched))
    us = _time(f, arr)
    traffic = hbm_traffic_model(rows, 2 * width + 1, lut, width)
    print(f"tap_fused_add_{rows}x{width}t,{us:.0f},"
          f"hbm_reduction={traffic['reduction_x']:.1f}x")


def bench_ternary(m: int = 256, k: int = 2048, n: int = 2048):
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (k, n), jnp.float32) * 0.02
    x = jax.random.normal(jax.random.PRNGKey(1), (m, k), jnp.float32)
    packed, scale = quantize_and_pack(w)
    f_t = jax.jit(lambda x, p, s: ternary_matmul_ref(x, p, s))
    f_d = jax.jit(lambda x, w: x @ w)
    us_t = _time(f_t, x, packed, scale)
    us_d = _time(f_d, x, w)
    bytes_bf16 = k * n * 2
    bytes_packed = (k // 16) * n * 4
    print(f"ternary_matmul_{m}x{k}x{n},{us_t:.0f},"
          f"dense_us={us_d:.0f}_weightbytes_bf16/packed="
          f"{bytes_bf16/bytes_packed:.0f}x")


def bench_apc(rows_list=(1024, 65536), widths=(8, 20),
              json_path: str | None = None) -> list[dict]:
    """AP program compiler vs interpreted replay: 20-digit ternary add.

    The interpreted path is :func:`repro.core.ap.ripple_add` with stats —
    per-pass python dispatch, ``int()`` host syncs every write cycle, host
    ``np.bincount`` per compare.  The apc path runs the whole flattened
    program in one pallas_call per row-block with in-graph counters.
    """
    results = []
    for width in widths:
        lut = build_lut_nonblocked(tt.full_adder(3))
        compiled = apc.compile_named("add", 3, width)
        for rows in rows_list:
            rng = np.random.default_rng(rows + width)
            a = rng.integers(0, 3 ** width, rows)
            b = rng.integers(0, 3 ** width, rows)
            arr = jnp.asarray(ap.encode_operands(a, b, 3, width))
            # interpreted pass-by-pass replay (the oracle path), stats on
            t0 = time.perf_counter()
            out_o = ap.ripple_add(arr, lut, width, 2 * width,
                                  stats=ap.APStats(radix=3))
            jax.block_until_ready(out_o)
            replay_us = (time.perf_counter() - t0) * 1e6
            # fused compiler path, stats on (and a stats-off variant)
            run_s = lambda: apc.execute(arr, compiled, collect_stats=True)
            run_p = lambda: apc.execute(arr, compiled, collect_stats=False)
            jax.block_until_ready(run_s()[0])       # compile
            t0 = time.perf_counter()
            out_f, traced = run_s()
            jax.block_until_ready((out_f, traced))
            apc_stats_us = (time.perf_counter() - t0) * 1e6
            jax.block_until_ready(run_p()[0])
            t0 = time.perf_counter()
            jax.block_until_ready(run_p()[0])
            apc_us = (time.perf_counter() - t0) * 1e6
            assert np.array_equal(np.asarray(out_o), np.asarray(out_f))
            row = {"op": "add", "radix": 3, "rows": rows, "width": width,
                   "replay_stats_us": round(replay_us),
                   "apc_stats_us": round(apc_stats_us),
                   "apc_us": round(apc_us),
                   "speedup_stats_x": round(replay_us / apc_stats_us, 2),
                   "speedup_pure_x": round(replay_us / apc_us, 2)}
            results.append(row)
            print(f"apc_add_{rows}x{width}t,{row['apc_stats_us']},"
                  f"replay={row['replay_stats_us']}us_"
                  f"speedup={row['speedup_stats_x']}x")
    if json_path:
        with open(json_path, "w") as f:
            json.dump({"bench": "apc_vs_replay", "results": results}, f,
                      indent=2)
        print(f"apc bench JSON -> {json_path}")
    return results


def _encode_named(fn: str, radix: int, width: int, rows: int, rng):
    """Random digit rows in the layout of a compile_named program."""
    a = rng.integers(0, radix ** width, rows)
    b = rng.integers(0, radix ** width, rows)
    if fn == "mul":
        arr = np.zeros((rows, 5 * width + 1), np.int8)
        for i in range(width):
            arr[:, i] = arr[:, width + i] = (a // radix ** i) % radix
            arr[:, 2 * width + i] = (b // radix ** i) % radix
        return jnp.asarray(arr)
    extra = 0 if fn in ("min", "max", "modsum", "nor", "nand") else 1
    return jnp.asarray(ap.encode_operands(a, b, radix, width,
                                          extra_cols=extra))


def bench_ap_matmul(mk_list=((4, 16), (16, 16), (16, 64)), n: int = 8,
                    radix: int = 3, max_abs: int = 3) -> list[dict]:
    """AP MAC-program matmul vs packed Pallas kernel vs jnp ref.

    Integer activations (the AP backend's exactness domain).  Wall time on
    the CPU host tells the simulator-cost story; the AP hardware story is the
    cycle/energy columns: all M*N outputs share one schedule, so compare/
    write cycles are (M, N)-independent and the Table XI model (1 nJ/set-or-
    reset, matchline compare energy) prices the whole matmul.
    """
    from repro.core.ap import APStats
    from repro.core.energy import T_WRITE_NS, energy_from_stats
    from repro.kernels.ternary_matmul.ap import (ap_matmul_cycle_counts,
                                                 ternary_matmul_ap)
    from repro.kernels.ternary_matmul.ops import ternary_matmul_op
    results = []
    for m, k in mk_list:
        rng = np.random.default_rng(m * k)
        w = jax.random.normal(jax.random.PRNGKey(k), (k, n), jnp.float32) * .05
        packed, scale = quantize_and_pack(w)
        x = jnp.asarray(rng.integers(-max_abs, max_abs + 1, (m, k)),
                        jnp.float32)
        from repro import apc
        width = apc.mac_acc_width(radix, k, max_abs)
        stats = APStats(radix=radix)
        y_ap = ternary_matmul_ap(x, packed, scale, radix=radix, stats=stats)
        y_ref = ternary_matmul_ref(x, packed, scale)
        assert np.array_equal(np.asarray(y_ap), np.asarray(y_ref))
        ap_us = _time(lambda: ternary_matmul_ap(x, packed, scale,
                                                radix=radix), n=3)
        pk_us = _time(lambda: ternary_matmul_op(x, packed, scale), n=3)
        rf_us = _time(lambda: ternary_matmul_ref(x, packed, scale), n=3)
        cyc = ap_matmul_cycle_counts(radix, packed.shape[0] * 16, width)
        # 3 LUT columns + 1 weight-predicate column per compare key
        rep = energy_from_stats(stats, n_masked=4)
        row = {"bench": "ap_matmul", "m": m, "k": k, "n": n, "radix": radix,
               "acc_width": width, "ap_us": round(ap_us),
               "packed_us": round(pk_us), "ref_us": round(rf_us),
               "write_cycles": cyc["write_cycles"],
               "compare_cycles": cyc["compare_cycles"],
               "ap_delay_ns": cyc["write_cycles"] * T_WRITE_NS
               + cyc["compare_cycles"] * 2.0,
               "energy_write_j": rep.write_energy_j,
               "energy_compare_j": rep.compare_energy_j,
               "energy_total_j": rep.total_j,
               "sets": int(rep.sets), "resets": int(rep.resets)}
        results.append(row)
        print(f"ap_matmul_{m}x{k}x{n},{row['ap_us']},"
              f"packed={row['packed_us']}us_writes={row['write_cycles']}"
              f"_E={row['energy_total_j']:.2e}J")
    return results


def bench_ap_pool(m: int = 8, k: int = 96, n: int = 8, radix: int = 3,
                  max_abs: int = 3, pool_rows: int = 16,
                  n_arrays_list=(1, 2, 4), k_tile_list=(8, 24),
                  n_timing: int = 3) -> list[dict]:
    """Array-pool pipelined executor: wall clock + write cycles vs
    (n_arrays, k_tile) under a fixed per-array column budget.

    Two scaling stories per row: ``wall_write_cycles`` is the PIPELINED
    hardware cost (ceil(n_blocks / n_arrays) replay waves per program —
    more arrays, fewer waves), ``write_cycles`` the schedule total charged
    to the energy model (sum of tile programs + reduction, independent of
    n_arrays).  Wall time on the CPU host tracks the simulator's dispatch
    pipelining.  Output is asserted bit-exact vs the jnp ref every run.
    """
    from repro.core.ap import APStats
    from repro.kernels.ternary_matmul.ap import ternary_matmul_ap
    results = []
    rng = np.random.default_rng(7)
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n), jnp.float32) * .05
    packed, scale = quantize_and_pack(w)
    kp = packed.shape[0] * 16
    x = jnp.asarray(rng.integers(-max_abs, max_abs + 1, (m, k)), jnp.float32)
    y_ref = ternary_matmul_ref(x, packed, scale)
    width = apc.mac_acc_width(radix, kp, max_abs)
    for k_tile in k_tile_list:
        cols = apc.mac_layout(min(k_tile, kp), width)["n_cols"]
        tiled = apc.compile_mac_tiled(radix, kp, width, k_tile,
                                      max_cols=cols)
        for n_arrays in n_arrays_list:
            pool = apc.ArrayPool(n_arrays=n_arrays, rows=pool_rows,
                                 cols=cols)
            stats = APStats(radix=radix)
            y = ternary_matmul_ap(x, packed, scale, radix=radix, pool=pool,
                                  stats=stats)
            assert np.array_equal(np.asarray(y), np.asarray(y_ref))
            us = _time(lambda: ternary_matmul_ap(x, packed, scale,
                                                 radix=radix, pool=pool),
                       n=n_timing)
            wall = pool.wall_cycles(m * n, tiled.n_compare_cycles,
                                    tiled.n_write_cycles)
            row = {"bench": "ap_pool", "m": m, "k": kp, "n": n,
                   "radix": radix, "acc_width": width, "k_tile": k_tile,
                   "n_tiles": len(tiled.tiles), "cols_budget": cols,
                   "pool_rows": pool_rows, "n_arrays": n_arrays,
                   "n_blocks": pool.n_blocks(m * n), "us": round(us),
                   "write_cycles": stats.n_write_cycles,
                   "compare_cycles": stats.n_compare_cycles,
                   "waves": wall["waves"],
                   "wall_write_cycles": wall["write_cycles"],
                   "wall_compare_cycles": wall["compare_cycles"]}
            results.append(row)
            print(f"ap_pool_{m}x{kp}x{n}_a{n_arrays}_kt{k_tile},"
                  f"{row['us']},waves={row['waves']}_wallwrites="
                  f"{row['wall_write_cycles']}")
    return results


def bench_ap_runtime(g_programs: int = 3, m: int = 6, k: int = 48,
                     n: int = 4, radix: int = 3, max_abs: int = 3,
                     pool_rows: int = 8, k_tile: int = 12,
                     n_arrays_list=(1, 2, 4), n_devices_list=(1,),
                     n_timing: int = 2) -> list[dict]:
    """Program-graph runtime vs naive sequential pool drains.

    ``g_programs`` independent (M, K, N) ternary matmuls — each a K-tiled
    MAC subgraph — run (a) sequentially, each drained through the pool via
    ``run_mac_tiled`` before the next starts, and (b) as ONE ProgramGraph
    through the Runtime.  Two scaling stories per row: wall clock of the
    simulator, and the occupancy model's ``makespan_cycles`` vs
    ``sequential_cycles`` (the modeled hardware win of pipelining
    independent programs into idle arrays).  ``n_devices > 1`` builds a
    DevicePool over a (d, 1) ("data", "model") mesh — rows appear only
    when the process actually has that many devices.  Bit-exactness vs the
    plain sum is asserted every run.
    """
    from jax.sharding import Mesh
    from repro.core.ap import APStats
    results = []
    rng = np.random.default_rng(12)
    width = apc.mac_acc_width(radix, k, max_abs)
    cols = apc.mac_layout(min(k_tile, k), width)["n_cols"]
    tiled = apc.compile_mac_tiled(radix, k, width, k_tile, max_cols=cols)
    macs, want = [], []
    for _ in range(g_programs):
        x = rng.integers(-max_abs, max_abs + 1, (m * n, k))
        w = rng.integers(-1, 2, (m * n, k))
        macs.append((jnp.asarray(x, jnp.int32), jnp.asarray(w, jnp.int8)))
        want.append((x * w).sum(axis=1))
    for n_devices in n_devices_list:
        if n_devices > len(jax.devices()):
            print(f"ap_runtime: skipping n_devices={n_devices} "
                  f"(only {len(jax.devices())} present)")
            continue
        mesh = None
        if n_devices > 1:
            devs = np.array(jax.devices()[:n_devices])
            mesh = Mesh(devs.reshape(n_devices, 1), ("data", "model"))
        for n_arrays in n_arrays_list:
            if mesh is None:
                pool = apc.ArrayPool(n_arrays=n_arrays, rows=pool_rows,
                                     cols=cols)
            else:
                pool = apc.DevicePool(mesh, n_arrays=n_arrays,
                                      rows=pool_rows, cols=cols)
            rt = apc.Runtime(pool)
            stats = APStats(radix=radix)
            digs = rt.run_mac_graph([(x, w, tiled) for x, w in macs],
                                    stats=stats)
            for d, wnt in zip(digs, want):
                got = apc.mac.decode_signed_digits_jnp(d, radix)
                assert np.array_equal(np.asarray(got), wnt)
            rep = rt.last_report

            def run_graph():
                return [jax.block_until_ready(d) for d in rt.run_mac_graph(
                    [(x, w, tiled) for x, w in macs])]

            def run_seq():
                return [jax.block_until_ready(apc.run_mac_tiled(
                    x, w, tiled, pool=pool)) for x, w in macs]

            us_rt = _time(run_graph, n=n_timing)
            us_seq = _time(run_seq, n=n_timing)
            row = {"bench": "ap_runtime", "g_programs": g_programs,
                   "m": m, "k": k, "n": n, "radix": radix,
                   "acc_width": width, "k_tile": k_tile,
                   "n_tiles": len(tiled.tiles), "cols_budget": cols,
                   "pool_rows": pool_rows, "n_arrays": n_arrays,
                   "n_devices": n_devices,
                   "n_arrays_total": n_arrays * n_devices,
                   "n_nodes": rep["n_nodes"],
                   "us_runtime": round(us_rt), "us_sequential": round(us_seq),
                   "makespan_cycles": rep["makespan_cycles"],
                   "sequential_cycles": rep["sequential_cycles"],
                   "makespan_ns": round(rep["makespan_ns"]),
                   "sequential_ns": round(rep["sequential_ns"]),
                   "pipeline_speedup_x": round(
                       rep["sequential_cycles"]
                       / max(1, rep["makespan_cycles"]), 2),
                   "write_cycles": stats.n_write_cycles,
                   "compare_cycles": stats.n_compare_cycles}
            results.append(row)
            print(f"ap_runtime_{g_programs}x{m}x{k}x{n}_d{n_devices}"
                  f"_a{n_arrays},{row['us_runtime']},"
                  f"makespan={row['makespan_cycles']}_seq="
                  f"{row['sequential_cycles']}"
                  f"_pipex={row['pipeline_speedup_x']}")
    return results


def bench_ap_sparse(m: int = 4, k: int = 40, n: int = 4, radix: int = 3,
                    max_abs: int = 3, k_tile: int = 10,
                    zero_fracs=(0.0, 0.3, 0.5, 0.7, 0.9),
                    pool_rows: int = 16, n_arrays: int = 2,
                    n_timing: int = 3,
                    json_path: str | None = None) -> list[dict]:
    """Sparsity-compressed MAC programs + weight-stationary resident bank
    ("ap_sparse" trajectory).

    For each weight zero-fraction (whole reduction columns zeroed, so the
    pass pruning is exact) the same K-tiled matmul runs two dataflows:
    streaming (weights re-encoded and re-uploaded per call) and resident
    (digit plane pinned once into the pool's ResidentStore, calls slice
    it).  Per row: schedule cycle counts pruned vs the dense baseline,
    wall-clock per call for both dataflows, and the host-side row-encode
    time each dataflow pays.  Bit-exactness streaming == resident == the
    integer reference is asserted every run.
    """
    from repro.apc.mac import (assemble_mac_rows_jnp, encode_mac_rows_jnp,
                               encode_mac_x_rows_jnp,
                               encode_weight_digits_jnp)
    results = []
    rng = np.random.default_rng(21)
    width = apc.mac_acc_width(radix, k, max_abs)
    cols = apc.mac_layout(min(k_tile, k), width)["n_cols"]
    dense = apc.compile_mac_tiled(radix, k, width, k_tile, max_cols=cols)
    x = jnp.asarray(rng.integers(-max_abs, max_abs + 1, (m, k)), jnp.int32)
    for zf in zero_fracs:
        w = rng.integers(-1, 2, (k, n))
        w[:, 0], w[:, 1] = 1, -1       # every live column keeps both sweeps
        n_zero_k = round(zf * k)
        w[rng.choice(k, size=n_zero_k, replace=False), :] = 0
        sup = apc.mac_weight_support(w.T)
        tiled = apc.compile_mac_tiled(radix, k, width, k_tile,
                                      max_cols=cols, support=sup)
        pool = apc.ArrayPool(n_arrays=n_arrays, rows=pool_rows, cols=cols)
        wj = jnp.asarray(w, jnp.int8)
        x_rows, w_rows = apc.mac.matmul_mac_rows(x, wj)
        handle = pool.resident.pin(
            f"bench:{zf}", apc.weight_digest(w.T),
            lambda _w=wj: encode_weight_digits_jnp(_w.T))

        def run_streaming():
            return apc.run_mac_tiled(x_rows, w_rows, tiled, pool=pool)

        def run_resident():
            return apc.run_mac_tiled(x_rows, None, tiled, pool=pool,
                                     resident=handle)

        y_s = np.asarray(run_streaming())
        y_r = np.asarray(run_resident())
        assert np.array_equal(y_s, y_r)
        want = np.asarray(x) @ w
        assert np.array_equal(y_s.reshape(m, n), want)
        us_s = _time(run_streaming, n=n_timing)
        us_r = _time(run_resident, n=n_timing)

        # host-side row-encode cost of each dataflow, in isolation: the
        # streaming path digitizes x AND the weight plane every call, the
        # resident path digitizes x and slices the pinned plane
        def enc_streaming():
            return encode_mac_rows_jnp(x_rows, w_rows, radix, width)

        plane = handle.resolve()

        def enc_resident():
            wd = jnp.tile(plane, (x_rows.shape[0] // plane.shape[0], 1))
            return assemble_mac_rows_jnp(
                encode_mac_x_rows_jnp(x_rows, radix, width), wd, width)

        enc_us_s = _time(enc_streaming, n=n_timing)
        enc_us_r = _time(enc_resident, n=n_timing)
        dense_w = tiled.dense_write_cycles or tiled.n_write_cycles
        row = {"bench": "ap_sparse", "m": m, "k": k, "n": n,
               "radix": radix, "acc_width": width, "k_tile": k_tile,
               "cols_budget": cols, "n_arrays": n_arrays,
               "zero_frac": round(zf, 2), "n_zero_k": n_zero_k,
               "emitted_passes": tiled.n_emitted_passes,
               "pruned_passes": tiled.n_pruned_passes,
               "write_cycles": tiled.n_write_cycles,
               "compare_cycles": tiled.n_compare_cycles,
               "dense_write_cycles": dense.n_write_cycles,
               "dense_compare_cycles": dense.n_compare_cycles,
               "write_cycle_reduction": round(
                   1 - tiled.n_write_cycles / dense_w, 4),
               "us_streaming": round(us_s), "us_resident": round(us_r),
               "encode_us_streaming": round(enc_us_s),
               "encode_us_resident": round(enc_us_r),
               "resident_hits": pool.resident.stats()["hits"]}
        results.append(row)
        print(f"ap_sparse_{m}x{k}x{n}_zf{zf},stream={row['us_streaming']}us,"
              f"resident={row['us_resident']}us,"
              f"writes={row['write_cycles']}/{row['dense_write_cycles']},"
              f"reduction={row['write_cycle_reduction']}")
    if json_path is not None and os.path.exists(json_path):
        # read-modify-write like trace_overhead: refresh this trajectory
        # without discarding the slow full-run results
        with open(json_path) as f:
            doc = json.load(f)
        doc["ap_sparse"] = results
        with open(json_path, "w") as f:
            json.dump(doc, f, indent=2)
        print(f"ap_sparse rows -> {json_path}")
    return results


def bench_trace_overhead(fn: str = "add", radix: int = 3, width: int = 20,
                         rows: int = 16384, n_timing: int = 5,
                         json_path: str | None = None) -> dict:
    """Telemetry cost on one compiled 20-trit add ("trace_overhead" row).

    Times the same compiled-program replay three ways: spans hard-off
    (``trace.disabled()`` — the REPRO_AP_TRACE=0 production path), spans
    recording into an active :class:`~repro.apc.trace.Tracer`, and the
    per-call cost of a no-op span front door in isolation.  The off path
    pays only a ContextVar read + shared-null-span return per instrumented
    call, so ``overhead_off_pct`` should sit inside timing noise (< 2%);
    ``overhead_traced_pct`` prices actually keeping the timeline.
    """
    from repro.apc import trace as aptrace
    compiled = apc.compile_named(fn, radix, width)
    rng = np.random.default_rng(7)
    arr = _encode_named(fn, radix, width, rows, rng)

    def run():
        out, _ = apc.execute(arr, compiled, collect_stats=False)
        return jax.block_until_ready(out)

    def timed(n):
        t0 = time.perf_counter()
        for _ in range(n):
            run()
        return (time.perf_counter() - t0) / n * 1e6

    with aptrace.disabled():
        run()                                  # compile once, off-path
        off_a = timed(n_timing)
    with aptrace.tracing(aptrace.Tracer()):
        traced_us = timed(n_timing)
    with aptrace.disabled():                   # interleave: drift control
        off_b = timed(n_timing)
    off_us = min(off_a, off_b)

    n_calls = 100_000
    with aptrace.disabled():
        t0 = time.perf_counter()
        for _ in range(n_calls):
            with aptrace.span("x", cat="bench"):
                pass
        noop_ns = (time.perf_counter() - t0) / n_calls * 1e9

    row = {"bench": "trace_overhead", "op": fn, "radix": radix,
           "width": width, "rows": rows, "n_steps": compiled.n_steps,
           "untraced_us": round(off_us), "untraced_runs_us":
               [round(off_a), round(off_b)],
           "traced_us": round(traced_us),
           "overhead_off_pct": round(100 * (max(off_a, off_b) / off_us - 1),
                                     2),
           "overhead_traced_pct": round(100 * (traced_us / off_us - 1), 2),
           "noop_span_ns": round(noop_ns)}
    print(f"trace_overhead_{fn}{radix}x{width}_{rows},"
          f"off={row['untraced_us']}us,traced={row['traced_us']}us,"
          f"traced_overhead={row['overhead_traced_pct']}%,"
          f"noop_span={row['noop_span_ns']}ns")
    if json_path is not None and os.path.exists(json_path):
        # read-modify-write: refresh just this row, keep the slow
        # trajectories from the last full run
        with open(json_path) as f:
            doc = json.load(f)
        doc["trace_overhead"] = row
        with open(json_path, "w") as f:
            json.dump(doc, f, indent=2)
        print(f"trace_overhead row -> {json_path}")
    return row


def main():
    import argparse
    p = argparse.ArgumentParser()
    p.add_argument("--full", action="store_true",
                   help="add the 1M-row tier (slow interpreted baseline)")
    p.add_argument("--json", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "apc_bench.json"))
    args = p.parse_args()
    enable_compile_cache(Path(__file__).resolve().parent.parent)
    bench_tap()
    bench_ternary()
    rows = (1024, 65536, 1048576) if args.full else (1024, 65536)
    # persist after each stage: the interpreted-replay baseline takes
    # minutes, so a later-stage failure must not discard it
    apc_rows = bench_apc(rows_list=rows, json_path=args.json)
    matmul_rows = bench_ap_matmul()
    pool_rows = bench_ap_pool()
    n_dev = len(jax.devices())
    runtime_rows = bench_ap_runtime(
        n_devices_list=(1,) if n_dev == 1 else (1, n_dev))
    sparse_rows = bench_ap_sparse()
    trace_row = bench_trace_overhead()
    with open(args.json, "w") as f:
        json.dump({"bench": "apc_vs_replay", "results": apc_rows,
                   "ap_matmul": matmul_rows,
                   "ap_pool": pool_rows, "ap_runtime": runtime_rows,
                   "ap_sparse": sparse_rows,
                   "trace_overhead": trace_row}, f, indent=2)
    print(f"apc bench JSON -> {args.json}")


if __name__ == "__main__":
    main()
