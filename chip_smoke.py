#!/usr/bin/env python3
"""Start AP-backed serving on a TPU chip and check what comes out.

    python3 chip_smoke.py                # one chip, three phases
    python3 chip_smoke.py --four-chips   # four chips, the mesh phase only

One chip, all in this one process:

- ``serve``: qwen3-0.6b at its published widths (depth cut to 1 layer) with
  its ternary MLPs on an ``ArrayPool`` at its own geometry (4 arrays x 4096
  rows x 256 columns).  Four 3-token prompts, 2 new tokens each, go through
  ``BatchServer``; each also runs alone through ``Engine.generate``.  The
  tokens must agree, and the batcher must have needed no wave abort, solo
  rerun, poisoning or failure to get them.
- ``projection``: one full-width ``APLinear`` (K=1024, N=3072, T=4) must
  decode to exactly ``x_int @ w_ter``, computed with numpy.
- ``program``: ``compile_named("add", 3, 8)`` must give the digits and
  ``APStats`` of the ``core/ap.py`` pass-by-pass replay.

``--four-chips`` runs the projection's graph on ``Runtime(DevicePool)`` over
a mesh of four chips and on a one-device ``ArrayPool``: digits, decoded
accumulators and ``APStats`` must be identical, and the work must land on
all four devices.

The interpret mode is read from its resolver and printed, never set.
The last line of stdout is one JSON object ``{"ok": true, "device":
{...}}``; a failed check, or a JAX backend that is not a TPU, exits
non-zero before printing it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

import jax                                                    # noqa: E402
import numpy as np                                            # noqa: E402

MODEL = "qwen3-0.6b"
N_REQUESTS, S_PROMPT, N_NEW = 4, 3, 2
PROJ_K, PROJ_N, PROJ_T = 1024, 3072, 4


class Failed(Exception):
    """A check of this script did not hold."""


def check(cond: bool, what: str) -> None:
    print(f"  check {'ok  ' if cond else 'FAIL'} {what}", flush=True)
    if not cond:
        raise Failed(what)


class CompileLog:
    """XLA backend compiles seen by JAX's monitoring hook; the program
    kernel's own jit (one Mosaic kernel each) is counted apart."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    KERNEL = "_tap_run_program_jit"

    def __init__(self):
        self.reset()

    def reset(self) -> None:
        self.n = self.kernel_n = 0
        self.secs = self.kernel_secs = 0.0

    def __call__(self, event: str, secs: float, **kw) -> None:
        if event != self.EVENT:
            return
        self.n += 1
        self.secs += secs
        if self.KERNEL in kw.get("fun_name", ""):
            self.kernel_n += 1
            self.kernel_secs += secs

    def line(self) -> str:
        return (f"compiles: {self.kernel_n} program-kernel (Mosaic) in "
                f"{self.kernel_secs:.3f}s; {self.n} XLA backend compiles in "
                f"all, {self.secs:.3f}s")


def serve_config():
    """qwen3-0.6b at its published widths, depth cut 28 -> 1 layer so that
    one chip's AP bank serves a request inside the run's time limit."""
    from repro.configs import TernaryCfg, get_config
    return get_config(MODEL).with_(n_layers=1,
                                   ternary=TernaryCfg(enabled=True))


def projection_programs(K: int, max_cols: int, x_levels: int):
    """The TiledMac an ``APLinear`` compiles for a K-wide projection."""
    from repro.apc.mac import compile_mac_tiled, mac_acc_width
    from repro.kernels.ternary_matmul.ap import default_k_tile
    width = mac_acc_width(3, K, x_levels)
    kt = min(default_k_tile(max_cols, width), K)
    return compile_mac_tiled(3, K, width, kt, max_cols=max_cols)


def describe_kernels(cfg, pool, x_levels: int) -> None:
    """Print the schedule lengths of every program the served MLP runs,
    and check the program kernel runs compiled."""
    from repro.kernels.tap_pass.kernel import resolve_interpret
    interp = resolve_interpret(None)
    for name, K in (("gate/up", cfg.d_model), ("down", cfg.d_ff)):
        tiled = projection_programs(K, pool.cols, x_levels)
        steps = {p.n_steps for p in tiled.programs + tiled.reduce_programs}
        print(f"  {name}: K={K} width={tiled.width} k_tile={tiled.k_tile} "
              f"tiles={len(tiled.tiles)} reduce={tiled.reduce_groups} "
              f"compare_cycles={tiled.n_compare_cycles} "
              f"write_cycles={tiled.n_write_cycles}", flush=True)
        print(f"    interpret={interp}, schedule steps per launch: "
              f"{sorted(steps)}", flush=True)
    check(not interp, "the program kernel runs compiled")


def phase_serve(*, rows: int = 4096, cols: int = 256, seed: int = 0) -> None:
    from serve_bench import build_engine
    from repro.apc.metrics import get_registry
    from repro.serve.batcher import AdmissionCfg, BatchServer
    cfg = serve_config()
    print(f"serve: {cfg.name} d_model={cfg.d_model} d_ff={cfg.d_ff} "
          f"heads={cfg.n_heads}/{cfg.n_kv_heads} head_dim={cfg.head_dim} "
          f"vocab={cfg.vocab} n_layers={cfg.n_layers} (published 28)",
          flush=True)
    t0 = time.perf_counter()
    eng = build_engine(cfg, rows=rows, cols=cols)
    pool = eng.ap_ctx.runtime.pool
    print(f"  pool: {pool!r}, x_levels={eng.ap_ctx.x_levels}; engine built "
          f"in {time.perf_counter() - t0:.3f}s", flush=True)
    describe_kernels(cfg, pool, eng.ap_ctx.x_levels)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, cfg.vocab, size=(1, S_PROMPT))
               for _ in range(N_REQUESTS)]
    reg = get_registry()
    watched = ("serve.wave_aborts", "serve.solo_reruns", "serve.poisoned",
               "serve.failed")
    before = {k: reg.counter(k).value for k in watched}
    t0 = time.perf_counter()
    with BatchServer(eng, admission=AdmissionCfg(
            max_inflight=N_REQUESTS)) as srv:
        handles = [srv.submit(p, N_NEW) for p in prompts]
        batched = [np.asarray(h.result(timeout=1800)) for h in handles]
        n_waves = srv.n_waves
    t_batched = time.perf_counter() - t0
    counts = {k: reg.counter(k).value - before[k] for k in watched}
    print(f"  batched: {N_REQUESTS} requests in {t_batched:.3f}s, "
          f"{n_waves} waves, counters {counts}", flush=True)
    t0 = time.perf_counter()
    sequential = [np.asarray(eng.generate(p, N_NEW)) for p in prompts]
    t_seq = time.perf_counter() - t0
    print(f"  sequential: {N_REQUESTS} requests in {t_seq:.3f}s", flush=True)
    for i, (p, b, s) in enumerate(zip(prompts, batched, sequential)):
        print(f"  request {i}: prompt {p.tolist()} batched {b.tolist()} "
              f"sequential {s.tolist()}", flush=True)
    check(all(np.array_equal(b, s) for b, s in zip(batched, sequential)),
          "batched tokens equal sequential tokens")
    check(all(b.shape == (1, N_NEW) for b in batched),
          f"every handle returned {N_NEW} tokens")
    check(all(v == 0 for v in counts.values()),
          "no wave abort, solo rerun, poisoning or failure")


def projection_graph(ctx, *, K: int, N: int, T: int, seed: int):
    """One APLinear on T random activations: (graph, call, x_int, w_ter)."""
    from repro.apc.graph import ProgramGraph
    from repro.apc.layers import APLinear
    key_w, key_x = jax.random.split(jax.random.PRNGKey(seed))
    w_ter = jax.random.randint(key_w, (K, N), -1, 2).astype(np.int8)
    lin = APLinear(w_ter, np.ones((N,), np.float32), label="proj")
    x_int, _ = ctx.quantize(jax.random.normal(key_x, (T, K), np.float32))
    graph = ProgramGraph()
    call = lin.add_call(graph, x_int, max_cols=ctx.max_cols,
                        max_q=ctx.x_levels)
    return graph, call, np.asarray(x_int), np.asarray(w_ter)


def run_projection(runtime, *, K: int, N: int, T: int, seed: int):
    """Run the projection graph on ``runtime``: (digits, acc, stats, want)."""
    from repro import apc
    from repro.core.ap import APStats
    ctx = apc.APServeContext(runtime, x_levels=7)
    graph, call, x_int, w_ter = projection_graph(ctx, K=K, N=N, T=T,
                                                 seed=seed)
    stats = APStats(radix=3)
    res = runtime.run_graph(graph, stats=stats)
    digits = np.asarray(res[call.node])
    acc = np.asarray(apc.decode_signed_digits_jnp(res[call.node], 3))
    want = (x_int.astype(np.int64) @ w_ter.astype(np.int64)).reshape(-1)
    return digits, acc.reshape(-1), stats, want


def phase_projection(*, rows: int = 4096, cols: int = 256,
                     seed: int = 1) -> None:
    from repro import apc
    print(f"projection: APLinear K={PROJ_K} N={PROJ_N} T={PROJ_T} on "
          f"ArrayPool(rows={rows}, cols={cols})", flush=True)
    t0 = time.perf_counter()
    rt = apc.Runtime(apc.ArrayPool(rows=rows, cols=cols))
    _, acc, stats, want = run_projection(rt, K=PROJ_K, N=PROJ_N, T=PROJ_T,
                                         seed=seed)
    print(f"  ran in {time.perf_counter() - t0:.3f}s: write_cycles="
          f"{stats.n_write_cycles} compare_cycles={stats.n_compare_cycles} "
          f"sets={stats.sets} resets={stats.resets}", flush=True)
    check(np.array_equal(acc, want),
          f"{acc.size} accumulators equal x_int @ w_ter exactly")


def stats_tuple(s) -> tuple:
    return (s.n_rows, s.n_compare_cycles, s.n_write_cycles, int(s.sets),
            int(s.resets), tuple(int(v) for v in s.mismatch_hist))


def phase_program(*, rows: int = 1000, width: int = 8, seed: int = 2
                  ) -> None:
    from repro import apc
    from repro.core import ap, build_lut_nonblocked
    from repro.core import truth_tables as tt
    from repro.kernels.tap_pass.kernel import resolve_interpret
    compiled = apc.compile_named("add", 3, width)
    interp = resolve_interpret(None)
    print(f"program: add r3 w{width}, {compiled.n_steps} steps, {rows} rows; "
          f"interpret={interp}", flush=True)
    check(not interp, "the program runs the compiled kernel")
    rng = np.random.default_rng(seed)
    a = rng.integers(0, 3 ** width, rows)
    b = rng.integers(0, 3 ** width, rows)
    arr = ap.encode_operands(a, b, 3, width)
    t0 = time.perf_counter()
    out, traced = apc.execute(jax.numpy.asarray(arr), compiled,
                              collect_stats=True)
    got = apc.to_ap_stats(traced, compiled, rows, 3)
    digits = np.asarray(out)
    print(f"  kernel ran in {time.perf_counter() - t0:.3f}s", flush=True)
    oracle = ap.APStats(radix=3)
    lut = build_lut_nonblocked(tt.full_adder(3))
    with jax.default_device(jax.devices("cpu")[0]):
        want = np.asarray(ap.ripple_add(jax.numpy.asarray(arr), lut, width,
                                        2 * width, stats=oracle))
    print(f"  stats {stats_tuple(got)} oracle {stats_tuple(oracle)}",
          flush=True)
    check(np.array_equal(digits, want), "digits equal the replay oracle")
    check(stats_tuple(got) == stats_tuple(oracle),
          "APStats equal the replay oracle")
    check(np.array_equal(ap.decode_digits(digits, list(range(width,
                                                             2 * width)), 3),
                         (a + b) % 3 ** width), "sums are right")


def phase_four_chips(devices, *, rows: int = 4096, cols: int = 256,
                     K: int = PROJ_K, N: int = PROJ_N, T: int = PROJ_T,
                     seed: int = 1) -> None:
    from jax.sharding import Mesh
    from repro import apc
    if len(devices) != 4:
        raise Failed(f"--four-chips needs 4 devices, found {len(devices)}")
    mesh = Mesh(np.array(devices).reshape(1, 4, 1), ("pod", "data", "model"))
    print(f"four-chips: APLinear K={K} N={N} T={T} on DevicePool over "
          f"{dict(mesh.shape)} vs ArrayPool on {devices[0]}", flush=True)
    dpool = apc.DevicePool(mesh, rows=rows, cols=cols)
    t0 = time.perf_counter()
    with mesh:
        d_dig, d_acc, d_st, want = run_projection(apc.Runtime(dpool), K=K,
                                                  N=N, T=T, seed=seed)
    print(f"  DevicePool ({dpool.n_devices} devices x {dpool.n_arrays} "
          f"arrays) ran in {time.perf_counter() - t0:.3f}s", flush=True)
    t0 = time.perf_counter()
    with jax.default_device(devices[0]):
        s_dig, s_acc, s_st, _ = run_projection(
            apc.Runtime(apc.ArrayPool(rows=rows, cols=cols)), K=K, N=N, T=T,
            seed=seed)
    print(f"  ArrayPool (1 device) ran in {time.perf_counter() - t0:.3f}s",
          flush=True)
    # where the mesh pool's blocks run: one tile program through the same
    # shard_map scaffolding, each device's output shard checked against the
    # one-device kernel on the rows it holds
    from repro.apc.exec import sharded_program_run
    prog = projection_programs(K, cols, 7).programs[0]
    n_rows = T * N
    rng = np.random.default_rng(seed)
    arr = rng.integers(0, 3, (4 * rows, prog.min_cols)).astype(np.int8)
    arr[n_rows:] = -1
    with mesh:
        out, _ = sharded_program_run(
            jax.numpy.asarray(arr),
            tuple(map(jax.numpy.asarray, prog.schedule_tensors)),
            mesh, dpool.axes, n_rows, rows, collect_stats=False,
            interpret=None)
    with jax.default_device(devices[0]):
        one, _ = apc.execute(jax.numpy.asarray(arr[:n_rows]), prog)
    one = np.asarray(one)
    shards = sorted((s.index[0].start or 0, s.device.id, np.asarray(s.data))
                    for s in out.addressable_shards)
    per_dev = [(dev, len(d)) for _, dev, d in shards]
    print(f"  one tile's output shards (device id, rows): {per_dev}",
          flush=True)
    shard_ok = all(np.array_equal(d[:max(0, min(len(d), n_rows - lo))],
                                  one[lo:lo + len(d)])
                   for lo, _, d in shards)
    print(f"  stats mesh {stats_tuple(d_st)} one-device {stats_tuple(s_st)}",
          flush=True)
    check(np.array_equal(d_dig, s_dig), "digits identical to one device")
    check(np.array_equal(d_acc, s_acc) and np.array_equal(d_acc, want),
          "decoded accumulators identical, and equal x_int @ w_ter")
    check(stats_tuple(d_st) == stats_tuple(s_st),
          "APStats identical to one device")
    check(len({d for d, _ in per_dev}) == 4 and shard_ok,
          "each of the four devices computed its rows of the tile")


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--four-chips", action="store_true",
                   help="run only the DevicePool-over-4-chips phase")
    args = p.parse_args()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (backend "
              f"{devices[0].platform!r}); this script runs only on the chip",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT / "benchmarks"))
    from repro.launch.compile_cache import enable_compile_cache
    print(f"device: {devices[0].device_kind} x {len(devices)}; compile cache "
          f"{enable_compile_cache(ROOT)}", flush=True)
    log = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(log)
    if args.four_chips:
        phases = [("four-chips", lambda: phase_four_chips(devices[:4]))]
    else:
        phases = [("program", phase_program),
                  ("projection", phase_projection),
                  ("serve", phase_serve)]
    walls = {}
    for name, fn in phases:
        log.reset()
        t0 = time.perf_counter()
        try:
            fn()
        except Failed as e:
            print(f"chip_smoke: phase {name} failed: {e}", file=sys.stderr)
            return 1
        walls[name] = round(time.perf_counter() - t0, 3)
        print(f"phase {name}: {walls[name]}s wall (compiles included); "
              f"this phase's {log.line()}", flush=True)
    print(f"walls: {walls}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
