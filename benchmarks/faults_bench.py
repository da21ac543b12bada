"""Serving-under-faults benchmark: throughput + recovery cost vs fault rate.

Drives the continuous-batching serve engine over a bank with a SEEDED
device fault model installed (``repro.apc.faults``) and sweeps the fault
intensity from a pristine zero-rate model up to a 1e-3 transient write-flip
rate with one array retired outright.  Each sweep point records one row of
the ``ap_faults`` trajectory::

    {"bench": "ap_faults", "flip_rate": ..., "n_dead": ..., "seed": ...,
     "achieved_rps": ..., "p50_ms": ..., "p99_ms": ...,
     "detected": ..., "retries": ..., "checksum_runs": ...,
     "surviving_arrays": ..., "n_arrays": ..., ...}

``detected``/``retries``/``checksum_runs`` are registry-counter deltas for
the point's run (how much recovery work the fault rate bought);
``surviving_arrays`` is the bank size left after any dynamic retirement.
Every request's tokens are verified against a fault-free reference engine
— the benchmark measures the COST of recovery, never silent corruption.

Usage::

    PYTHONPATH=src python benchmarks/faults_bench.py [--smoke] [--record]

``--smoke`` shrinks the sweep to a seconds-scale CI gate; ``--record``
writes the rows into benchmarks/apc_bench.json (read-modify-write,
keeping the other trajectories).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import numpy as np                                            # noqa: E402

from repro import apc                                         # noqa: E402
from repro.apc.metrics import get_registry                    # noqa: E402
from repro.launch.compile_cache import enable_compile_cache    # noqa: E402
from repro.serve.batcher import AdmissionCfg, BatchServer     # noqa: E402

from serve_bench import build_engine                          # noqa: E402

_FAULT_COUNTERS = ("faults.detected", "faults.retries",
                   "faults.checksum_runs", "faults.retired")


def run_fault_point(flip_rate: float, dead: tuple[int, ...], *,
                    n_requests: int = 4, n_new: int = 3, s_prompt: int = 3,
                    n_arrays: int = 4, max_inflight: int = 8,
                    seed: int = 2, reference: list | None = None) -> dict:
    """Serve ``n_requests`` over a bank with the given fault intensity;
    returns one ``ap_faults`` row.  ``reference`` (optional, filled on
    first call) carries the fault-free token arrays every later point is
    verified against."""
    faults = None
    if flip_rate > 0 or dead:
        # transient flips at 1e-3 are EXPECTED to trip detections steadily;
        # a low retire_after would mistake that for permanent damage and
        # bury the whole bank, so retirement is reserved for the explicit
        # dead_arrays point of the sweep
        faults = apc.FaultConfig(flip_rate=flip_rate, dead_arrays=dead,
                                 seed=seed, max_retries=6,
                                 retire_after=10_000)
    eng = build_engine(n_arrays=n_arrays, faults=faults)
    if faults is None and eng.ap_ctx.runtime.pool.fault_model is None:
        # zero-rate point: install the model explicitly so the checksum
        # verify path (the detection overhead) is on and priced
        pool = eng.ap_ctx.runtime.pool
        pool.fault_model = apc.FaultModel(
            apc.FaultConfig(seed=seed), pool.n_arrays, pool.rows,
            pool.cols)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, eng.cfg.vocab, size=(1, s_prompt))
               for _ in range(n_requests)]

    reg = get_registry()
    base = reg.counter_values(_FAULT_COUNTERS)
    t0 = time.perf_counter()
    with BatchServer(eng, admission=AdmissionCfg(
            max_inflight=max_inflight)) as srv:
        handles = [srv.submit(p, n_new) for p in prompts]
        tokens = [np.asarray(h.result(timeout=600)) for h in handles]
        n_waves = srv.n_waves
    wall = time.perf_counter() - t0
    delta = {k: reg.counter_values(_FAULT_COUNTERS)[k] - base[k]
             for k in base}
    if reference is not None:
        if not reference:
            reference.extend(tokens)
        else:
            for i, (got, want) in enumerate(zip(tokens, reference)):
                if not np.array_equal(got, want):
                    raise SystemExit(
                        f"ap_faults: request {i} tokens diverged at "
                        f"flip_rate={flip_rate} dead={dead} — recovery "
                        f"let corruption through")
    fm = eng.ap_ctx.runtime.pool.fault_model
    lats = np.asarray([h.latency_ms for h in handles], np.float64)
    row = {
        "bench": "ap_faults",
        "flip_rate": flip_rate,
        "n_dead": len(dead),
        "seed": seed,
        "n_arrays": n_arrays,
        "n_requests": n_requests,
        "s_prompt": s_prompt,
        "n_new": n_new,
        "max_inflight": max_inflight,
        "achieved_rps": round(n_requests / wall, 3),
        "p50_ms": round(float(np.percentile(lats, 50)), 2),
        "p99_ms": round(float(np.percentile(lats, 99)), 2),
        "n_waves": n_waves,
        "detected": delta["faults.detected"],
        "retries": delta["faults.retries"],
        "checksum_runs": delta["faults.checksum_runs"],
        "retired": delta["faults.retired"],
        "surviving_arrays": len(fm.healthy()),
        "wall_s": round(wall, 3),
    }
    print(f"ap_faults flip={flip_rate} dead={len(dead)} "
          f"rps={row['achieved_rps']} p99={row['p99_ms']}ms "
          f"detected={row['detected']} retries={row['retries']} "
          f"surviving={row['surviving_arrays']}/{n_arrays}")
    return row


def sweep(points, **kw) -> list[dict]:
    reference: list = []
    return [run_fault_point(fr, dead, reference=reference, **kw)
            for fr, dead in points]


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--smoke", action="store_true",
                   help="seconds-scale sweep: the CI faults gate")
    p.add_argument("--record", action="store_true",
                   help="write the ap_faults trajectory into apc_bench.json")
    p.add_argument("--json", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "apc_bench.json"))
    args = p.parse_args()
    enable_compile_cache(Path(__file__).resolve().parent.parent)
    if args.smoke:
        points = [(0.0, ()), (1e-3, (1,))]
        kw = dict(n_requests=3, n_new=2)
    else:
        points = [(0.0, ()), (1e-4, ()), (1e-3, ()), (1e-3, (1,))]
        kw = dict(n_requests=4, n_new=3)
    rows = sweep(points, **kw)
    if args.record:
        with open(args.json) as f:
            doc = json.load(f)
        doc["ap_faults"] = rows
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=2)
        print(f"ap_faults trajectory -> {args.json}")


if __name__ == "__main__":
    main()
