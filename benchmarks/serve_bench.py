"""Sustained-traffic benchmark of the continuous-batching AP serve engine.

Offers a Poisson-ish open-loop request stream (fixed inter-arrival gap) to a
:class:`repro.serve.batcher.BatchServer` over the smallest real AP-backed
Engine (packed-ternary MLP through the program-graph runtime), and reports
the serving curve: achieved requests/sec and p50/p99 request latency vs
offered load, plus wave/merge occupancy (how many source graph nodes the
batcher folded into how many merged launches).

Each sweep point is recorded as one row of the ``ap_serve`` trajectory::

    {"bench": "ap_serve", "offered_rps": ..., "achieved_rps": ...,
     "p50_ms": ..., "p99_ms": ..., "n_requests": ..., "max_inflight": ...,
     "n_waves": ..., "queued": ..., "rejected": ..., "max_queue_depth": ...}

The admission-control columns record how load shedding behaved at that
offered rate: ``queued`` counts requests that waited in the pending deque
at least once, ``rejected`` counts policy="reject" sheds, and
``max_queue_depth`` is the deepest the pending deque ever got.

Usage::

    PYTHONPATH=src python benchmarks/serve_bench.py [--smoke] [--record]

``--smoke`` shrinks the sweep to a seconds-scale CI gate; ``--record``
writes the rows into benchmarks/apc_bench.json (read-modify-write, keeping
the other trajectories).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import jax                                                    # noqa: E402
import numpy as np                                            # noqa: E402

from repro import apc                                         # noqa: E402
from repro.configs import (ModelConfig, TernaryCfg,           # noqa: E402
                           get_smoke_config)
from repro.launch.compile_cache import enable_compile_cache    # noqa: E402
from repro.launch.mesh import make_smoke_mesh                 # noqa: E402
from repro.models import model as M                           # noqa: E402
from repro.models.quant import quantize_model_params          # noqa: E402
from repro.serve.batcher import AdmissionCfg, BatchServer     # noqa: E402
from repro.serve.engine import Engine, ServeCfg               # noqa: E402


def toy_config() -> ModelConfig:
    """The bench's model: the qwen3-0.6b smoke config cut to 1 layer at
    ``d_model=16``, ``d_ff=24``, vocab 32, with ternary MLPs."""
    base = get_smoke_config("qwen3-0.6b")
    return base.with_(n_layers=1, d_model=16, d_ff=24, n_heads=2,
                      n_kv_heads=2, head_dim=8, vocab=32,
                      ternary=TernaryCfg(enabled=True))


def build_engine(cfg: ModelConfig | None = None, *, n_arrays: int = 4,
                 rows: int = 64, cols: int = 64, faults=None) -> Engine:
    """An Engine whose packed-ternary MLPs run on an AP bank of
    ``n_arrays`` x ``rows`` x ``cols`` arrays.

    ``cfg`` (default :func:`toy_config`) must enable ternary packing;
    weights are random from a fixed seed.  ``faults`` (a
    :class:`repro.apc.FaultConfig`) installs the seeded device fault model
    on the bank — the faults_bench sweep and the degraded-bank smoke gate
    use it."""
    cfg = toy_config() if cfg is None else cfg
    mesh = make_smoke_mesh()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    qparams = quantize_model_params(params)
    pool = apc.ArrayPool(n_arrays=n_arrays, rows=rows, cols=cols,
                         faults=faults)
    ctx = apc.APServeContext(apc.Runtime(pool), x_levels=7)
    return Engine(cfg, qparams, mesh, ServeCfg(max_len=8), ap_ctx=ctx)


def run_load_point(offered_rps: float, n_requests: int, *,
                   max_inflight: int = 8, s_prompt: int = 3,
                   n_new: int = 3, seed: int = 0) -> dict:
    """Offer ``n_requests`` at ``offered_rps`` (open loop); one row."""
    eng = build_engine()
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, eng.cfg.vocab, size=(1, s_prompt))
               for _ in range(n_requests)]
    gap = 1.0 / offered_rps if offered_rps > 0 else 0.0
    t0 = time.perf_counter()
    with BatchServer(eng, admission=AdmissionCfg(
            max_inflight=max_inflight)) as srv:
        handles = []
        for p in prompts:
            handles.append(srv.submit(p, n_new))
            if gap:
                time.sleep(gap)
        for h in handles:
            h.result(timeout=600)
        n_waves = srv.n_waves
        n_queued, n_rejected = srv.n_queued, srv.n_rejected
        max_queue_depth = srv.max_queue_depth
    wall = time.perf_counter() - t0
    lats = np.asarray([h.latency_ms for h in handles], np.float64)
    row = {
        "bench": "ap_serve",
        "offered_rps": round(offered_rps, 3),
        "achieved_rps": round(n_requests / wall, 3),
        "p50_ms": round(float(np.percentile(lats, 50)), 2),
        "p99_ms": round(float(np.percentile(lats, 99)), 2),
        "mean_ms": round(float(lats.mean()), 2),
        "n_requests": n_requests,
        "s_prompt": s_prompt,
        "n_new": n_new,
        "max_inflight": max_inflight,
        "n_waves": n_waves,
        "queued": n_queued,
        "rejected": n_rejected,
        "max_queue_depth": max_queue_depth,
        "wall_s": round(wall, 3),
    }
    print(f"ap_serve offered={row['offered_rps']}rps "
          f"achieved={row['achieved_rps']}rps p50={row['p50_ms']}ms "
          f"p99={row['p99_ms']}ms waves={n_waves} queued={n_queued} "
          f"depth={max_queue_depth}")
    return row


def degraded_bank_smoke(*, n_requests: int = 3, n_new: int = 2) -> None:
    """CI gate: serving stays green on a degraded bank (one array retired
    at construction), with tokens identical to the pristine bank's."""
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 32, size=(1, 3)) for _ in range(n_requests)]

    eng_ok = build_engine()
    want = [np.asarray(eng_ok.generate(p, n_new)) for p in prompts]

    eng = build_engine(faults=apc.FaultConfig(dead_arrays=(1,)))
    with BatchServer(eng, admission=AdmissionCfg(max_inflight=4)) as srv:
        handles = [srv.submit(p, n_new) for p in prompts]
        got = [np.asarray(h.result(timeout=600)) for h in handles]
        n_waves = srv.n_waves
    assert n_waves > 0
    assert eng.ap_ctx.runtime.pool.dead_arrays == (1,)
    for i, (g, w) in enumerate(zip(got, want)):
        assert np.array_equal(g, w), \
            f"degraded-bank smoke: request {i} tokens diverged"
    print(f"degraded-bank smoke: {n_requests} requests on 3/4 arrays, "
          f"tokens bit-identical to the pristine bank")


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--smoke", action="store_true",
                   help="seconds-scale sweep: the CI serve gate")
    p.add_argument("--record", action="store_true",
                   help="write the ap_serve trajectory into apc_bench.json")
    p.add_argument("--json", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "apc_bench.json"))
    args = p.parse_args()
    enable_compile_cache(Path(__file__).resolve().parent.parent)
    if args.smoke:
        points = [(4.0, 4), (16.0, 6)]
    else:
        points = [(0.5, 8), (2.0, 12), (8.0, 16), (32.0, 16)]
    rows = [run_load_point(rps, n) for rps, n in points]
    if args.smoke:
        degraded_bank_smoke()
    if args.record:
        with open(args.json) as f:
            doc = json.load(f)
        doc["ap_serve"] = rows
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=2)
        print(f"ap_serve trajectory -> {args.json}")


if __name__ == "__main__":
    main()
