#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` on the chip this process is given.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration and a traffic mix; both are files found by
name (``bench/configs/<config>.json``, ``bench/traffic/<traffic>.json``).
The configuration names its driver (``bench/drivers/<driver>.py``), which
builds the system from the seed, warms every shape the mix uses (set-up),
drives the mix for ``--seconds`` (the window), and checks what the window
produced against the plain reference under ``bench/reference/``.  Every
metric is a reader of its own, ``bench/metrics/<metric>.py``, over the
facts the run gathered (``run.metric_file``); ``--trace 0`` prints the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and with ``--trace 1``,
``breakdown``), and last ``checks``, each number compared with its limit.
The same checks are the last lines of stderr.  Without an accelerator, or
with fewer chips than the cell asks for, it exits 2 and prints no result.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


def load_module(path: Path):
    """Import a file of the benchmark by its path (names may hold dots)."""
    if not path.is_file():
        raise FileNotFoundError(f"no benchmark file {path}")
    name = "bench_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_files(bench: dict, workload: str) -> dict:
    """Everything a cell is made of, found by the names in BENCHMARK.json."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(ROOT / configs[cell["config"]]["file"])

    def mine(entries):
        return [m for m in entries
                if "workloads" not in m or workload in m["workloads"]]

    return {"cell": cell, "config": config,
            "traffic": load_json(BENCH / "traffic" / f"{cell['traffic']}.json"),
            "driver": load_module(BENCH / "drivers" / f"{config['driver']}.py"),
            "end_to_end": mine(bench["end_to_end"]),
            "per_layer": mine(bench["per_layer"])}


def accelerator(chips: int):
    """The devices of the run; raises NoChip without an accelerator."""
    import jax
    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise NoChip("JAX found no accelerator (backend 'cpu'); the "
                     "benchmark measures only on the chip")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices


def peaks_for(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json; "
                       f"add its peaks with their source")
    return table["devices"][kind]


def use_compile_cache() -> str:
    """JAX's persistent cache at a fixed path in the checkout, unless
    ``JAX_COMPILATION_CACHE_DIR`` names one; every compile is kept."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class GcPauses:
    """Full (generation 2) collections of the garbage collector, with the
    seconds each took, while it is entered."""

    def __init__(self):
        self.pauses: list[float] = []
        self._t0 = 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if info.get("generation") != 2:
            return
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.pauses.append(time.perf_counter() - self._t0)

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)


class CompileCount:
    """Backend compiles seen by JAX's monitoring hook."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.names: list[str] = []

    @property
    def n(self) -> int:
        return len(self.names)

    def __call__(self, event: str, secs: float, **kw) -> None:
        if event == self.EVENT:
            self.names.append(str(kw.get("fun_name", "?")))


def counters() -> dict[str, int]:
    """The program's integer counters, by name."""
    from repro.apc.metrics import get_registry
    return {k: v for k, v in get_registry().snapshot().items()
            if isinstance(v, int)}


def traced_window(driver, state, seconds: float, trace: bool):
    """Drive the window, under the profiler when ``trace``; returns the
    driver's facts and, traced, the trace reduction."""
    import jax
    if not trace:
        with jax.profiler.TraceAnnotation("bench.window"):
            return driver.window(state, seconds), None
    from trace_reduce import reduce_trace
    log_dir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                facts = driver.window(state, seconds)
        finally:
            jax.profiler.stop_trace()
        return facts, reduce_trace(log_dir)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)


def metric_file(name: str) -> Path:
    """A metric's reader: ``bench/metrics/<name>.py``; for a name
    ``<quantity>.<part>`` with no file of its own, the quantity's reader
    ``bench/metrics/<quantity>.py``, which serves every part (a quantity
    split by the end-to-end metric that it moves)."""
    own = BENCH / "metrics" / f"{name}.py"
    if own.is_file() or "." not in name:
        return own
    return BENCH / "metrics" / f"{name.split('.')[0]}.py"


def read_metrics(entries: list, facts: dict) -> dict:
    """Each metric's reader over the facts; one that finds nothing to read
    is left out."""
    out = {}
    for m in entries:
        value = load_module(metric_file(m["name"])).read(facts)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             bench: dict | None = None, config_override: dict | None = None,
             traffic_override: dict | None = None,
             check_kwargs: dict | None = None,
             require_chip: bool = True) -> tuple[dict, list]:
    """One run of one cell: ``(result line, checks)``.

    ``config_override`` and ``traffic_override`` replace keys of the
    configuration and the mix, ``check_kwargs`` go to ``check`` of the
    configuration's module in ``bench/drivers`` (the control,
    ``bench/control.py``, uses both), and
    ``require_chip=False`` skips the look for a chip: the benchmark's own
    tests drive a run at a small size on the CPU with them."""
    import jax
    bench = bench or load_json(ROOT / "BENCHMARK.json")
    files = cell_files(bench, workload)
    config = dict(files["config"], **(config_override or {}))
    chips = int(files["cell"]["chips"])
    devices = accelerator(chips) if require_chip else jax.devices()
    kind = devices[0].device_kind
    peaks = peaks_for(kind) if trace and require_chip else None
    driver = files["driver"]
    compiles = CompileCount()
    jax.monitoring.register_event_duration_secs_listener(compiles)

    t0 = time.perf_counter()
    traffic = dict(files["traffic"], **(traffic_override or {}))
    state = driver.setup(config, traffic, seed)
    setup_s = time.perf_counter() - t0
    before, c0 = counters(), compiles.n
    with GcPauses() as gc_pauses:
        facts, reduced = traced_window(driver, state, seconds, trace)
    after, in_window = counters(), compiles.names[c0:]
    facts.update(setup_s=setup_s, peaks=peaks, trace=reduced,
                 compiles_in_window=len(in_window),
                 counters={k: v - before.get(k, 0) for k, v in after.items()})
    stats = devices[0].memory_stats() or {}
    peak_bytes = int(stats.get("peak_bytes_in_use", 0))
    checks = driver.check(state, facts, seed, **(check_kwargs or {}))
    del state

    entries = files["per_layer"] if trace else files["end_to_end"]
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    line = {"correct": all(c["value"] <= c["limit"] for c in checks),
            "attempted": int(facts["attempted"]),
            "failed": int(facts["failed"]),
            "metrics": read_metrics(entries, facts),
            "device": device}
    if trace:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
    line["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                      for c in checks}
    print(f"run: {workload} seed={seed} setup_s={setup_s:.3f} "
          f"window_s={facts['window_s']:.3f} compiles_in_window="
          f"{facts['compiles_in_window']} {in_window[:20]} full_gc_s="
          f"{[round(t, 3) for t in gc_pauses.pauses]}",
          file=sys.stderr, flush=True)
    return line, checks


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    use_compile_cache()
    try:
        line, checks = run_cell(args.workload, args.seed, args.seconds,
                                bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for c in checks:
        ok = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {c['name']} {c['value']!r} limit {c['limit']!r} {ok}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
