#!/usr/bin/env python3
"""One traced run of a cell, with device idle time read by program span.

    python3 bench/span_report.py --workload <cell> --seed <n> --seconds <s>

Runs the cell as ``bench/run.py --trace 1`` does and prints its result
line, with the cell's end-to-end metrics as read over the traced window
(the profiler on) and, under ``spans``, what ``bench/span_reduce.py``
reads from the program's own spans in the same trace: per-layer idle
shares, host microseconds per launch, and the seconds behind them.  The
harness's reduction is wrapped for the run, not changed.  Exits 2 without
a chip, as ``run.py`` does.
"""
from __future__ import annotations

import argparse
import json
import sys

import run
import span_reduce
import trace_reduce


def report(workload: str, seed: int, seconds: float, **cell) -> dict:
    """The traced run's result line with its ``spans``; ``cell`` goes to
    ``run.run_cell`` (the benchmark's CPU tests run a small size)."""
    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    bench["per_layer"] = bench["per_layer"] + bench["end_to_end"]
    reduce, seen = trace_reduce.reduce_trace, {}

    def reduce_with_spans(log_dir):
        out = reduce(log_dir)
        seen.update(span_reduce.reduce_spans(log_dir),
                    window_s=out["window_s"])
        return out

    trace_reduce.reduce_trace = reduce_with_spans
    try:
        line, _ = run.run_cell(workload, seed, seconds, True, bench=bench,
                               **cell)
    finally:
        trace_reduce.reduce_trace = reduce
    line["spans"] = {
        "metrics": span_reduce.metrics(seen, seen["window_s"]),
        "host_s": seen["host_s"], "idle_s": seen["idle_s"]}
    return line


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    args = p.parse_args(argv)
    sys.path.insert(0, str(run.ROOT / "src"))
    run.use_compile_cache()
    try:
        line = report(args.workload, args.seed, args.seconds)
    except run.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
