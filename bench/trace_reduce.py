"""Reduce a profiler trace (``.xplane.pb``) to the numbers the readers use.

The window is the host annotation ``bench.window`` that the harness puts
around its measured window; everything is clipped to it.  On each device
plane the ``XLA Ops`` line holds one event per operation that ran:

- busy time is the union of those events' intervals, averaged over the
  devices that ran any;
- ``op_seconds`` sums each operation's time by name (the kernel readers
  pick their kernel from it);
- idle gaps are the holes in the first busy device's union, each labelled
  with what the host was doing at the gap's middle: the innermost
  ``bench.*`` annotation and the innermost other host event there.

Device operation names are the HLO instruction's text; the breakdown keeps
its name and first result shape (``_tap_run_program_jit.1 s8[4096,41]``).
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

import numpy as np

WINDOW = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
PAINT_BUCKETS = 1 << 21      # time resolution of the idle-gap labels
TOP = 10


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {files}")
    return files[0]


def union(intervals: np.ndarray) -> np.ndarray:
    """Sorted, merged [start, end) intervals of an (n, 2) array."""
    if not len(intervals):
        return intervals.reshape(0, 2)
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, np.int64)


def _events(line):
    return [(e.name, int(e.start_ns), int(e.end_ns)) for e in line.events]


def read_planes(path: str):
    """(device ops per device plane, host events, window [start, end))."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    devices: dict[str, list] = {}
    host: list[tuple[str, int, int]] = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.setdefault(plane.name, []).extend(_events(line))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(_events(line))
    windows = [(s, e) for n, s, e in host if n == WINDOW]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {WINDOW!r} annotation in the "
                           f"trace, found {len(windows)}")
    return devices, host, windows[0]


def reduce_events(devices: dict[str, list], host: list, window: tuple
                  ) -> dict:
    """The reduction proper, on plain (name, start_ns, end_ns) events."""
    w0, w1 = window
    busy, op_ns = [], defaultdict(int)
    first_union = None
    for name in sorted(devices):
        evs = [(n, max(s, w0), min(e, w1)) for n, s, e in devices[name]]
        evs = [(n, s, e) for n, s, e in evs if e > s]
        if not evs:
            continue
        for n, s, e in evs:
            op_ns[n] += e - s
        u = union(np.asarray([(s, e) for _, s, e in evs], np.int64))
        busy.append(int((u[:, 1] - u[:, 0]).sum()))
        if first_union is None:
            first_union = u
    out = {"window_s": (w1 - w0) / 1e9,
           "busy_s": (sum(busy) / len(busy) / 1e9) if busy else 0.0,
           "n_devices_busy": len(busy),
           "op_seconds": {n: ns / 1e9 for n, ns in op_ns.items()}}
    short: dict[str, int] = defaultdict(int)
    for n, ns in op_ns.items():
        short[short_name(n)] += ns
    out["device_ops"] = [[n, ns / 1e9] for n, ns in sorted(
        short.items(), key=lambda kv: -kv[1])[:TOP]]
    out["idle_gaps"] = idle_gaps(first_union, host, window)
    return out


def short_name(op: str) -> str:
    """``%name = (shape{layout}, ...) op(...)`` -> ``name shape``."""
    if " = " not in op:
        return op
    head, rest = op.split(" = ", 1)
    return f"{head.lstrip('%')} {rest.lstrip('(').split('{')[0].split(' ')[0]}"


def _paint(events: list, t0: int, t1: int) -> tuple[np.ndarray, list]:
    """Per time bucket of [t0, t1), the index of the innermost (shortest)
    event covering it, or -1."""
    scale = PAINT_BUCKETS / max(1, t1 - t0)
    owner = np.full(PAINT_BUCKETS, -1, np.int64)
    names = [n for n, _, _ in events]
    for i in sorted(range(len(events)),
                    key=lambda i: events[i][1] - events[i][2]):
        _, s, e = events[i]
        lo = max(0, int((s - t0) * scale))
        hi = min(PAINT_BUCKETS, int(np.ceil((e - t0) * scale)))
        if hi > lo:
            owner[lo:hi] = i
    return owner, names


def idle_gaps(u, host: list, window: tuple) -> list:
    """Idle time of one device by host label, the largest first."""
    w0, w1 = window
    if u is None:
        return [["no device op in the window", (w1 - w0) / 1e9]]
    edges = np.concatenate([[w0], u.ravel(), [w1]]).reshape(-1, 2)
    gaps = edges[edges[:, 1] > edges[:, 0]]
    if not len(gaps):
        return []
    inside = [(n, s, e) for n, s, e in host
              if n != WINDOW and e > w0 and s < w1]
    bench, b_names = _paint([x for x in inside if x[0].startswith("bench.")],
                            w0, w1)
    other, o_names = _paint([x for x in inside
                             if not x[0].startswith("bench.")], w0, w1)
    mid = ((gaps.sum(axis=1) // 2 - w0) * PAINT_BUCKETS
           // max(1, w1 - w0)).clip(0, PAINT_BUCKETS - 1)
    by_label: dict[str, int] = defaultdict(int)
    for (g0, g1), m in zip(gaps, mid):
        ann = b_names[bench[m]] if bench[m] >= 0 else "outside bench calls"
        act = o_names[other[m]] if other[m] >= 0 else "no host event"
        by_label[f"{ann} > {act}"] += int(g1 - g0)
    return [[k, v / 1e9] for k, v in sorted(
        by_label.items(), key=lambda kv: -kv[1])[:TOP]]


def reduce_trace(log_dir: str) -> dict:
    devices, host, window = read_planes(find_xplane(log_dir))
    return reduce_events(devices, host, window)


def kernel_seconds(op_seconds: dict, pattern: str) -> float:
    """Device time of the operations whose name matches ``pattern``."""
    rx = re.compile(pattern)
    return sum(s for n, s in op_seconds.items() if rx.search(n))
