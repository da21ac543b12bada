#!/usr/bin/env python3
"""Read a cell's correctness numbers for the program and for its control.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

For each seed, a run with the control that the configuration file names
under ``control``.  A switch of the reference (``check``) reads the control
beside the program in that one run (the check returns both); a switch of
the program (``config``) needs a run of the program too, which comes first.
Prints one JSON line per run; the control has to come out not correct on
every seed.  All runs share this one process, so set-up compiles once.
"""
from __future__ import annotations

import argparse
import json
import sys

import run


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, required=True)
    args = p.parse_args(argv)
    sys.path.insert(0, str(run.ROOT / "src"))
    run.use_compile_cache()
    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    control = run.cell_files(bench, args.workload)["config"]["control"]
    sides = [("control", {"config_override": control.get("config"),
                           "check_kwargs": control.get("check")})]
    if "config" in control:
        sides.insert(0, ("program", {}))
    for seed in (int(s) for s in args.seeds.split(",")):
        for side, kw in sides:
            try:
                line, checks = run.run_cell(args.workload, seed, args.seconds,
                                            False, bench=bench, **kw)
            except run.NoChip as e:
                print(f"control: {e}", file=sys.stderr)
                return 2
            print(json.dumps({"side": side, "seed": seed,
                              "correct": line["correct"], "checks": checks,
                              "metrics": line["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
