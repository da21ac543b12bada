"""Device idle time by the program's own spans, from a profiler trace.

The program opens a profiler annotation at each layer boundary of the AP
stack (``ap.*``, named in ``repro.apc.trace``).  On the profiler's clock
they sit beside the device's ``XLA Ops``, so each instant of device idle
time can be charged to what the host was doing then:

- ``host_s``: per span name, ``[count, seconds]`` inside the window,
  summed over threads;
- ``idle_s``: the idle time of the first busy device (all of the window
  without one), split exactly by the span the host was in at each instant:
  on each thread its innermost span; across threads a span in which the
  host works wins over one in which it waits (``WAIT_SPANS``), then the
  shorter.  Idle time under no span goes under ``""``, so the values sum to
  the window minus that device's busy time, ``device_idle``'s complement.

``metrics`` turns both into per-layer readings: the idle shares of
``IDLE_LAYERS`` and ``launch_host_us``, host microseconds per launch.
The harness does not read them yet: ``bench/span_report.py`` runs a cell
traced and prints them beside the cell's own line.
"""
from __future__ import annotations

import numpy as np

import trace_reduce

SPAN_PREFIX = "ap."
# program spans in which the host waits rather than works (repro.apc.trace)
WAIT_SPANS = frozenset({"ap.serve.wave", "ap.serve.rendezvous",
                        "ap.pool.drain", "ap.stats.sync"})


# per-layer reading -> which spans' idle time it counts ("" for none); the
# five idle readings of a cell partition its device idle time
IDLE_LAYERS = {
    "idle_dispatch": lambda n: n.startswith(("ap.runtime.", "ap.pool.")),
    "idle_sync": lambda n: n == "ap.stats.sync",
    "idle_batcher": lambda n: n.startswith("ap.serve."),
    "idle_model": lambda n: n.startswith("ap.model."),
    "idle_unspanned": lambda n: n == "",
}
LAUNCH_SPAN = "ap.pool.launch"


def read_host_spans(path: str) -> list[tuple[str, int, int, int]]:
    """``(name, start_ns, end_ns, thread)`` of every program span on the
    host planes of an ``.xplane.pb``; the thread is the event's line."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    out, thread = [], 0
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out.extend((e.name, int(e.start_ns), int(e.end_ns), thread)
                       for e in line.events
                       if e.name.startswith(SPAN_PREFIX))
            thread += 1
    return out


def first_busy_union(devices: dict[str, list], window: tuple):
    """The busy union of the first device (by name) with an operation in
    the window, as ``trace_reduce.reduce_events`` picks it; None if none."""
    w0, w1 = window
    for name in sorted(devices):
        iv = [(max(s, w0), min(e, w1)) for _, s, e in devices[name]]
        iv = [x for x in iv if x[1] > x[0]]
        if iv:
            return trace_reduce.union(np.asarray(iv, np.int64))
    return None


def idle_intervals(u, window: tuple) -> np.ndarray:
    """The holes of busy union ``u`` inside the window, (n, 2)."""
    w0, w1 = window
    if u is None:
        return np.asarray([[w0, w1]], np.int64)
    edges = np.concatenate([[w0], u.ravel(), [w1]]).reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


def span_times(u, spans: list, window: tuple) -> dict:
    """``{"host_s": ..., "idle_s": ...}`` (module docstring) of the program
    spans ``(name, start_ns, end_ns, thread)`` against busy union ``u``."""
    w0, w1 = window
    spans = [(n, max(s, w0), min(e, w1), t) for n, s, e, t in spans
             if n.startswith(SPAN_PREFIX)]
    spans = [x for x in spans if x[2] > x[1]]
    host_s: dict[str, list] = {}
    for n, s, e, _ in spans:
        c = host_s.setdefault(n, [0, 0.0])
        c[0] += 1
        c[1] += (e - s) / 1e9
    # idle time up to an instant: linear inside a gap, flat between gaps
    gaps = idle_intervals(u, window)
    lens = gaps[:, 1] - gaps[:, 0]
    xs = (gaps - w0).ravel().astype(np.float64)
    fs = np.stack([np.cumsum(lens) - lens, np.cumsum(lens)], 1).ravel()
    # sweep the span boundaries; between two, every thread's open spans are
    # fixed, and its innermost is the one opened last (of two opened at
    # once, the shorter)
    marks = sorted([(s, 1, s - e, i) for i, (_, s, e, _) in enumerate(spans)]
                   + [(e, 0, 0, i) for i, (_, _, e, _) in enumerate(spans)])
    open_by_thread: dict[int, list] = {}
    starts, owners = [w0], [""]
    for t, is_start, _, i in marks:
        stack = open_by_thread.setdefault(spans[i][3], [])
        if is_start:
            stack.append(i)
        else:
            stack.remove(i)
        tops = [spans[st[-1]] for st in open_by_thread.values() if st]
        best = min(tops, key=lambda x: (x[0] in WAIT_SPANS, x[2] - x[1]),
                   default=("",))[0]
        if t == starts[-1]:
            owners[-1] = best
        else:
            starts.append(t)
            owners.append(best)
    bounds = np.interp(np.asarray(starts + [w1], np.int64) - w0, xs, fs,
                       left=0.0, right=float(lens.sum()))
    idle_s: dict[str, float] = {"": 0.0}
    for name, ns in zip(owners, np.diff(bounds)):
        idle_s[name] = idle_s.get(name, 0.0) + float(ns) / 1e9
    return {"host_s": host_s, "idle_s": idle_s}


def reduce_spans(log_dir: str) -> dict:
    """:func:`span_times` of the one trace under ``log_dir``, against the
    device and window that ``trace_reduce.reduce_trace`` reads there."""
    path = trace_reduce.find_xplane(log_dir)
    devices, _, window = trace_reduce.read_planes(path)
    return span_times(first_busy_union(devices, window),
                      read_host_spans(path), window)


def metrics(spans: dict, window_s: float) -> dict[str, float]:
    """The per-layer readings (per cent of the window, and host
    microseconds per launch); empty where the program put no span on the
    profiler's clock, as a program before these spans does."""
    if not spans["host_s"] or not window_s:
        return {}
    idle = spans["idle_s"]
    out = {name: 100.0 * sum(v for k, v in idle.items() if counts(k))
           / window_s for name, counts in IDLE_LAYERS.items()}
    count, secs = spans["host_s"].get(LAUNCH_SPAN, (0, 0.0))
    if count:
        out["launch_host_us"] = 1e6 * secs / count
    return out
