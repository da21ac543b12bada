"""The one traffic generator: reads a mix from ``bench/traffic/<name>.json``.

A mix names its loop (``closed``: each client sends its next job when its
last one is answered), its client count, and the fields of a job.  Each
field lists the values it takes::

    {"values": [1, 2, 3]}                                  the values given
    {"range": 4}                                           0, 1, 2, 3
    {"log_uniform_int": {"lo": 64, "hi": 16384, "n": 32}}  n quantiles

The jobs of one cycle are the cross product of the fields, so every seed
sends the same set of jobs; the seed only shuffles their order.  That keeps
the work of a window fixed from seed to seed.
"""
from __future__ import annotations

import itertools
import math
import threading
import time

import numpy as np


def field_values(spec: dict) -> list[int]:
    """The values one job field takes, in a fixed order."""
    if "values" in spec:
        return [int(v) for v in spec["values"]]
    if "range" in spec:
        return list(range(int(spec["range"])))
    if "log_uniform_int" in spec:
        p = spec["log_uniform_int"]
        lo, hi, n = math.log(p["lo"]), math.log(p["hi"]), int(p["n"])
        return [int(round(math.exp(lo + (i + 0.5) / n * (hi - lo))))
                for i in range(n)]
    raise ValueError(f"unknown job field spec {spec}")


def job_cycle(traffic: dict, seed: int) -> list[dict]:
    """One cycle of jobs: the cross product of the fields, in an order
    drawn from ``seed``."""
    names = sorted(traffic["job"])
    grid = [dict(zip(names, combo)) for combo in itertools.product(
        *(field_values(traffic["job"][n]) for n in names))]
    order = np.random.default_rng(seed).permutation(len(grid))
    return [grid[i] for i in order]


def distinct(traffic: dict, name: str) -> list[int]:
    """Every value a field takes (what set-up has to warm)."""
    return sorted(set(field_values(traffic["job"][name])))


class JobFeed:
    """Thread-safe endless feed of numbered jobs, cycling the seed's order."""

    def __init__(self, traffic: dict, seed: int):
        self._cycle = job_cycle(traffic, seed)
        self._next = 0
        self._lock = threading.Lock()

    def take(self) -> tuple[int, dict]:
        with self._lock:
            i = self._next
            self._next += 1
        return i, self._cycle[i % len(self._cycle)]


def closed_loop(traffic: dict, seed: int, seconds: float, do_job
                ) -> tuple[list, float]:
    """Run ``traffic["clients"]`` closed-loop clients for ``seconds``.

    Each client takes the next job and calls ``do_job(index, job)``, which
    returns when the job is answered, with a record of it.  No client starts
    a job after ``seconds``; the window ends when every started job is
    answered.  Returns ``(records in start order, window seconds)``.
    """
    if traffic.get("loop") != "closed":
        raise ValueError(f"only closed loops are generated, got "
                         f"{traffic.get('loop')!r}")
    feed = JobFeed(traffic, seed)
    records: dict[int, object] = {}
    errors: list[BaseException] = []
    t0 = time.perf_counter()
    stop_at = t0 + seconds

    def client():
        try:
            while time.perf_counter() < stop_at:
                i, job = feed.take()
                records[i] = do_job(i, job)
        except BaseException as e:          # surfaced after the join
            errors.append(e)

    threads = [threading.Thread(target=client, name=f"bench-client{c}")
               for c in range(int(traffic["clients"]))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    window_s = time.perf_counter() - t0
    if errors:
        raise errors[0]
    return [records[i] for i in sorted(records)], window_s
