"""Driver of a pooled AP program: vector jobs through ``run_pooled``.

Set-up compiles the configuration's program, builds the bank, makes the
operand sets on the device from the seed (uniform random digits, carry 0),
cuts every job size the mix uses from them, and runs each size once.  The
window runs the mix's closed loop; a job is one ``run_pooled`` call with a
fresh ``APStats``, timed until its digits are on the device and its
counters on the host.  The check replays every operand set with the plain
reference: each job's APStats must equal the reference's, and the digits of
a sample of jobs drawn from the seed must equal the reference's, exactly.
"""
from __future__ import annotations

import threading
import time

import numpy as np

import loadgen
import work
from reference import tap_add


def _key(seed: int):
    import jax
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, seed >> 32)


def make_operands(seed: int, n_sets: int, rows: int, radix: int, width: int):
    """[n_sets, rows, 2w+1] int8 digit rows in one jitted call: A and B
    digits uniform over the radix (uniform operands), carry column 0."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key):
        d = jax.random.randint(key, (n_sets, rows, 2 * width), 0, radix,
                               jnp.int32).astype(jnp.int8)
        return jnp.concatenate(
            [d, jnp.zeros((n_sets, rows, 1), jnp.int8)], axis=-1)

    return make(_key(seed))


class Sample:
    """A uniform sample of ``k`` job outputs, drawn from the seed
    (reservoir sampling: memory stays bounded however many jobs run)."""

    def __init__(self, k: int, seed: int):
        self.k = k
        self.rng = np.random.default_rng([seed, 1])
        self.kept: dict[int, object] = {}
        self.seen = 0
        self.lock = threading.Lock()

    def offer(self, i: int, out) -> None:
        with self.lock:
            self.seen += 1
            if len(self.kept) < self.k:
                self.kept[i] = out
                return
            j = int(self.rng.integers(self.seen))
            if j < self.k:
                self.kept.pop(sorted(self.kept)[j])
                self.kept[i] = out


def setup(config: dict, traffic: dict, seed: int) -> dict:
    import jax
    from repro import apc
    from repro.apc.pool import ArrayPool, run_pooled
    from repro.core.ap import APStats
    radix, width = int(config["radix"]), int(config["width"])
    prog = apc.compile_named(config["op"], radix, width,
                             blocked=config["lut"] == "blocked")
    pool = ArrayPool(**config["assumed"]["bank"])
    sizes = loadgen.distinct(traffic, "rows")
    sets = loadgen.distinct(traffic, "operand_set")
    operands = make_operands(seed, len(sets), max(sizes), radix, width)
    inputs = {(r, s): operands[s, :r] for r in sizes for s in sets}
    jax.block_until_ready(list(inputs.values()))
    for r in sizes:                      # compile every shape the mix uses
        out = run_pooled(inputs[(r, sets[0])], prog, pool,
                         stats=APStats(radix=radix))
        out.block_until_ready()
    return {"config": config, "traffic": traffic, "seed": seed,
            "prog": prog, "pool": pool, "inputs": inputs,
            "operands": operands, "run_pooled": run_pooled,
            "APStats": APStats, "radix": radix, "width": width}


def stats_tuple(st) -> tuple:
    return (int(st.n_rows), int(st.n_compare_cycles), int(st.n_write_cycles),
            int(st.sets), int(st.resets),
            tuple(int(v) for v in st.mismatch_hist))


def window(state: dict, seconds: float) -> dict:
    import jax
    prog, pool, radix = state["prog"], state["pool"], state["radix"]
    run_pooled, APStats = state["run_pooled"], state["APStats"]
    sample = Sample(int(state["traffic"]["check_sample"]), state["seed"])

    def job(i, j):
        x = state["inputs"][(j["rows"], j["operand_set"])]
        st = APStats(radix=radix)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.run_pooled"):
            out = run_pooled(x, prog, pool, stats=st)
            out.block_until_ready()
        latency = time.perf_counter() - t0
        sample.offer(i, out)
        return {"rows": j["rows"], "set": j["operand_set"],
                "latency_s": latency, "stats": stats_tuple(st)}

    records, window_s = loadgen.closed_loop(
        state["traffic"], state["seed"], seconds, job)
    total: dict = {}
    n_cols = 2 * state["width"] + 1
    for r in loadgen.distinct(state["traffic"], "rows"):
        n = sum(1 for rec in records if rec["rows"] == r)
        work.add_work(total, work.run_work(prog, r, pool.rows, n_cols), n)
    state["records"], state["sample"] = records, sample.kept
    return {"window_s": window_s, "jobs": len(records),
            "rows": sum(rec["rows"] for rec in records),
            "job_latency_s": [rec["latency_s"] for rec in records],
            "kernel_work": total, "attempted": len(records), "failed": 0}


def prefix_sums(v) -> np.ndarray:
    """Row 0 is zeros; row r sums the first r rows of ``v``."""
    v = np.asarray(v, np.int64)
    return np.concatenate([np.zeros((1,) + v.shape[1:], np.int64),
                           np.cumsum(v, axis=0)])


def reference_tables(operands, radix: int, width: int):
    """Per operand set: reference digits and prefix sums of the per-row
    counters, so the stats of the first ``r`` rows are one lookup."""
    out = []
    for s in range(operands.shape[0]):
        digits, sets, resets, hist = tap_add.replay(operands[s], radix, width)
        out.append({"digits": digits, "sets": prefix_sums(sets),
                    "resets": prefix_sums(resets), "hist": prefix_sums(hist)})
    return out


def expected_stats(table: dict, rows: int, radix: int, width: int,
                   hist_len: int) -> tuple:
    n_cmp, n_wr = tap_add.cycles(radix, width)
    hist = np.zeros(hist_len, np.int64)
    hist[:tap_add.HIST_BINS] = table["hist"][rows]
    return (rows, n_cmp, n_wr, int(table["sets"][rows]),
            int(table["resets"][rows]), tuple(int(v) for v in hist))


def check(state: dict, facts: dict, seed: int) -> list[dict]:
    records, kept = state["records"], state["sample"]
    radix, width = state["radix"], state["width"]
    operands = state["operands"]
    for k in ("pool", "inputs", "prog"):            # the program's state
        state.pop(k, None)
    tables = reference_tables(operands, radix, width)
    hist_len = len(records[0]["stats"][5]) if records else tap_add.HIST_BINS
    bad_stats = sum(
        1 for rec in records
        if rec["stats"] != expected_stats(tables[rec["set"]], rec["rows"],
                                          radix, width, hist_len))
    bad_rows = 0
    for i, out in kept.items():
        rec = records[i]
        want = np.asarray(tables[rec["set"]]["digits"][:rec["rows"]])
        got = np.asarray(out)
        bad_rows += (int(np.any(got != want, axis=1).sum())
                     if got.shape == want.shape else rec["rows"])
    return [{"name": "jobs_with_wrong_apstats", "value": bad_stats,
             "limit": 0},
            {"name": "sampled_rows_with_wrong_digits", "value": bad_rows,
             "limit": 0},
            {"name": "jobs_sampled_missing", "value": int(not kept),
             "limit": 0}]
