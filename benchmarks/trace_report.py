"""End-to-end AP telemetry report: serve one AP-backed request under a
Tracer and export the Perfetto timeline + per-phase attribution table.

Builds the smallest AP-backed :class:`repro.serve.engine.Engine` that
routes real packed-ternary projections through the program-graph runtime
(the tests' smoke recipe), runs a single ``generate()`` request with
tracing active, then:

- writes the Chrome/Perfetto ``trace_event`` JSON (open it at
  https://ui.perfetto.dev or chrome://tracing): pid 0 is host
  orchestration (request / prefill / decode / compile / pool waves /
  runtime wavefronts as nested slices), pid 1 is AP *model time* (one
  track per device/array, each slice a scheduled program interval);
- prints the per-phase cycle/energy attribution table and asserts it sums
  **bit-exactly** to the request's aggregated APStats / Table XI energy —
  the tentpole acceptance check;
- validates the exported JSON against the trace_event schema.

Usage::

    PYTHONPATH=src python benchmarks/trace_report.py [--out PATH] [--smoke]

``--smoke`` skips the table pretty-print and keeps the run minimal — the
CI trace step uses it as the telemetry end-to-end gate.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import jax                                                    # noqa: E402
import numpy as np                                            # noqa: E402

from repro import apc                                         # noqa: E402
from repro.apc import trace                                   # noqa: E402
from repro.apc.metrics import get_registry                    # noqa: E402
from repro.configs import get_smoke_config                    # noqa: E402
from repro.core.energy import energy_from_stats               # noqa: E402
from repro.launch.mesh import make_smoke_mesh                 # noqa: E402
from repro.models import model as M                           # noqa: E402
from repro.models.quant import quantize_model_params          # noqa: E402
from repro.serve.engine import Engine, ServeCfg               # noqa: E402


def build_engine() -> Engine:
    """Smallest Engine whose MLPs really run on the AP runtime."""
    base = get_smoke_config("qwen3-0.6b")
    cfg = base.with_(n_layers=1, d_model=16, d_ff=24, n_heads=2,
                     n_kv_heads=2, head_dim=8, vocab=32,
                     ternary=base.ternary.__class__(enabled=True))
    mesh = make_smoke_mesh()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    qparams = quantize_model_params(params)
    pool = apc.ArrayPool(n_arrays=4, rows=64, cols=64)
    ctx = apc.APServeContext(apc.Runtime(pool), x_levels=7)
    return Engine(cfg, qparams, mesh, ServeCfg(max_len=8), ap_ctx=ctx)


def run_request(eng: Engine, n_new: int = 2) -> tuple[trace.Tracer, dict]:
    tracer = trace.Tracer()
    with trace.tracing(tracer):
        toks = eng.generate(np.array([[3]], dtype=np.int32), n_new)
        assert toks.shape == (1, n_new)
        report = eng.ap_report()
    return tracer, report


def check_attribution(tracer: trace.Tracer, eng: Engine) -> None:
    """The tentpole invariant: per-phase attribution sums bit-exactly to
    the request's aggregated APStats, and Table XI energy matches."""
    ctx = eng.ap_ctx
    st = tracer.total_ap_stats(ctx.radix)
    agg = ctx.stats
    assert st.sets == agg.sets, (st.sets, agg.sets)
    assert st.resets == agg.resets, (st.resets, agg.resets)
    assert st.n_compare_cycles == agg.n_compare_cycles
    assert st.n_write_cycles == agg.n_write_cycles
    assert np.array_equal(st.mismatch_hist, agg.mismatch_hist)
    from repro.apc.layers import N_MASKED_MAC
    e_trace = energy_from_stats(st, n_masked=N_MASKED_MAC).total_j
    e_ctx = energy_from_stats(agg, n_masked=N_MASKED_MAC).total_j
    assert e_trace == e_ctx, (e_trace, e_ctx)


def check_power(report: dict, events: list) -> None:
    """Power observability invariants: the report's per-array power rollup
    integrates to the request's Table XI energy bit-exactly, and the
    exported trace carries counter ("C") tracks for it."""
    pw = report["power"]
    assert pw["energy_j"] == report["energy_total_j"], \
        (pw["energy_j"], report["energy_total_j"])
    assert pw["per_array"], "power rollup has no per-array entries"
    counters = [e for e in events if e["ph"] == "C"]
    assert counters, "trace export carries no power counter events"
    names = {e["name"] for e in counters}
    assert "ap.power" in names and "ap.power.bank" in names, names


def print_power_table(report: dict) -> None:
    """Per-array power rollup table (``--power``)."""
    pw = report["power"]
    print("\n== per-array power (Table XI energy / model time) ==")
    hdr = f"{'array':<12}{'energy (J)':>14}{'busy (ns)':>12}" \
          f"{'avg (W)':>12}{'peak (W)':>12}"
    print(hdr)
    print("-" * len(hdr))
    for track, e in pw["per_array"].items():
        print(f"{track:<12}{e['energy_j']:>14.3e}{e['busy_ns']:>12.1f}"
              f"{e['avg_w']:>12.4f}{e['peak_w']:>12.4f}")
    print("-" * len(hdr))
    print(f"{'BANK':<12}{pw['energy_j']:>14.3e}"
          f"{pw['model_span_ns']:>12.1f}{pw['avg_w']:>12.4f}"
          f"{pw['peak_w']:>12.4f}")
    print(f"  hottest array: {pw['hottest_array']}   "
          f"timelines folded: {pw['n_timelines']}")


def print_tables(tracer: trace.Tracer, report: dict) -> None:
    print("\n== per-phase cycle/energy attribution ==")
    hdr = f"{'phase':<12}{'programs':>9}{'compare':>10}{'write':>10}" \
          f"{'sets':>10}{'resets':>10}{'energy (J)':>14}"
    print(hdr)
    print("-" * len(hdr))
    for phase, tot in (report.get("phases") or {}).items():
        print(f"{phase:<12}{tot['programs']:>9}{tot['compare_cycles']:>10}"
              f"{tot['write_cycles']:>10}{tot['sets']:>10}"
              f"{tot['resets']:>10}{tot['energy_total_j']:>14.3e}")
    print("-" * len(hdr))
    print(f"{'TOTAL':<12}{'':>9}{report['compare_cycles']:>10}"
          f"{report['write_cycles']:>10}{report['sets']:>10}"
          f"{report['resets']:>10}{report['energy_total_j']:>14.3e}")

    print("\n== request latency (host) ==")
    for k, v in (report.get("latency") or {}).items():
        print(f"  {k:<18}{v:>12.3f}" if isinstance(v, float)
              else f"  {k:<18}{v:>12}")

    print("\n== compile / serving caches ==")
    cache = report.get("cache") or {}
    for name, info in (cache.get("compile") or {}).items():
        print(f"  {name:<22}hits={info['hits']:<6}misses={info['misses']:<6}"
              f"size={info['currsize']}/{info['maxsize']}")
    print(f"  pool_schedules        "
          f"{cache.get('pool_schedules')}/{cache.get('pool_schedules_max')}")
    print(f"  stacks                {cache.get('stacks')}")

    print("\n== scheduler ==")
    print(f"  makespan_cycles       {report['makespan_cycles']}")
    print(f"  sequential_cycles     {report['sequential_cycles']}")
    seq = report["sequential_cycles"]
    if seq:
        print(f"  parallel speedup      "
              f"{seq / max(1, report['makespan_cycles']):.2f}x")

    print("\n== metrics registry ==")
    for name, snap in sorted(get_registry().snapshot().items()):
        print(f"  {name:<26}{snap}")


def main(argv=None) -> int:
    ap_ = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap_.add_argument("--out", default="benchmarks/ap_trace.json",
                     help="Perfetto trace_event JSON output path")
    ap_.add_argument("--n-new", type=int, default=2,
                     help="decode steps in the traced request")
    ap_.add_argument("--smoke", action="store_true",
                     help="CI mode: validate + assert, minimal printing")
    ap_.add_argument("--power", action="store_true",
                     help="print the per-array power rollup table")
    args = ap_.parse_args(argv)

    eng = build_engine()
    tracer, report = run_request(eng, n_new=args.n_new)

    doc = tracer.to_chrome()
    events = trace.validate_chrome_trace(doc)
    check_attribution(tracer, eng)
    check_power(report, events)
    assert report["phases"], "tracer active but report carries no phases"

    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(doc))
    spans = sum(1 for e in events if e["ph"] == "X")
    model = sum(1 for e in events
                if e["ph"] == "X" and e["pid"] == trace.MODEL_PID)
    counters = sum(1 for e in events if e["ph"] == "C")
    print(f"wrote {out} ({len(events)} events: {spans} spans, "
          f"{model} model-time slices, {counters} power samples, "
          f"{len(tracer.attributions)} attributions) — "
          f"open at https://ui.perfetto.dev")
    if args.smoke:
        print("smoke OK: schema valid, attribution + power bit-exact")
        return 0
    print_tables(tracer, report)
    if args.power:
        print_power_table(report)
    return 0


if __name__ == "__main__":
    sys.exit(main())
