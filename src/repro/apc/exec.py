"""Fused sharded executor for compiled AP programs.

One program launch per row-block replays the ENTIRE flattened program
against the resident tile — a 20-trit add (421 steps) or a shift-and-add
multiply (thousands of steps) costs one HBM read + one HBM write per block
instead of one round-trip per pass.  Long schedules stay cheap to trace: the
kernel fori-loops over the dense schedule tensors
(:class:`~repro.apc.lower.CompiledProgram`).  The kernel has one body, the
``lanes`` layout (:mod:`repro.kernels.tap_pass.kernel`); ``interpret=``
picks how it runs — Mosaic on TPU, the pallas interpreter or the
jitted-XLA harness elsewhere — and defaults per backend.

Rows are the data-parallel axis. :func:`execute` runs on whatever device
holds the array; :func:`execute_sharded` shard_maps row-blocks over the
("pod", "data") axes of a :mod:`repro.launch.mesh` device mesh, psumming the
traced counters so every shard returns the global stats; :func:`run` with
``pool=`` streams row blocks over a bank of bounded MvCAM arrays
(:mod:`repro.apc.pool`) instead of assuming one unbounded array — a
:class:`repro.apc.runtime.DevicePool` there spans the bank over mesh
devices, and whole dependency DAGs of programs schedule through
:class:`repro.apc.runtime.Runtime` rather than this single-program door.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from ..core.ap import APStats
from ..kernels.tap_pass.kernel import tap_run_program
from ..kernels.tap_pass.ops import _pad_rows
from ..launch.mesh import data_axes
from . import trace
from .ir import Program
from .lower import CompiledProgram, compile_program
from .stats import HIST_BINS, TracedStats, accumulate

BLOCK_ROWS = 4096        # fused-program default: fewer, fatter row-blocks


def execute(arr: jax.Array, compiled: CompiledProgram, *,
            collect_stats: bool = False, block_rows: int | None = None,
            interpret: bool | None = None
            ) -> tuple[jax.Array, TracedStats | None]:
    """Run a compiled program on [rows, cols] int8 digits.

    Returns ``(out, traced)``; ``traced`` is ``None`` unless
    ``collect_stats`` — stats cost extra in-kernel reductions, so the pure
    path skips them entirely (static flag, separate compiled kernel).
    """
    rows, cols = arr.shape
    if cols < compiled.min_cols:
        raise ValueError(
            f"array has {cols} columns, program touches {compiled.min_cols}")
    if rows == 0:                       # empty batch: no launch, zero counts
        traced = TracedStats(jnp.zeros((1, 2 + HIST_BINS), jnp.int32))
        return jnp.asarray(arr, jnp.int8), traced if collect_stats else None
    block_rows = block_rows or min(BLOCK_ROWS, max(8, rows))
    padded, _ = _pad_rows(jnp.asarray(arr, jnp.int8), block_rows)
    with trace.span("execute", cat="execute", rows=rows,
                    steps=compiled.n_steps):
        out, raw = tap_run_program(
            padded, *compiled.schedule_tensors, jnp.int32(rows),
            block_rows=block_rows, collect_stats=collect_stats,
            hist_bins=HIST_BINS, interpret=interpret)
    out = out[:rows]
    return out, (TracedStats(block_counts=raw) if collect_stats else None)


def sharded_program_run(padded: jax.Array, sched: tuple, mesh, axes,
                        rows: int, block_rows: int, *,
                        collect_stats: bool, interpret: bool | None
                        ) -> tuple[jax.Array, jax.Array]:
    """shard_map scaffolding shared by :func:`execute_sharded` and
    :class:`repro.apc.runtime.DevicePool`: split ``padded`` (rows already a
    multiple of shards x block_rows) over ``axes``, run the dense
    ``sched`` tensors per shard with padding rows masked via each shard's
    global row offset, and psum the raw counter tensor across shards so
    every shard returns the GLOBAL counts.  Returns ``(out, raw)`` with
    ``out`` still padded (caller slices) and ``raw`` meaningful only when
    ``collect_stats``."""
    n_shards = math.prod(mesh.shape[a] for a in axes)
    shard_rows = padded.shape[0] // n_shards

    def per_shard(a):
        # global row index of this shard's first row -> how many of its rows
        # are real (the tail shard sees the padding)
        idx = jnp.int32(0)
        for ax in axes:
            idx = idx * mesh.shape[ax] + jax.lax.axis_index(ax)
        n_local = jnp.clip(rows - idx * shard_rows, 0, shard_rows)
        out, raw = tap_run_program(
            a, *sched, n_local, block_rows=block_rows,
            collect_stats=collect_stats, hist_bins=HIST_BINS,
            interpret=interpret)
        if collect_stats:
            # elementwise-add the (n_blocks, counters) tensors across shards;
            # the int64 total reduction stays on the host (stats.accumulate)
            return out, jax.lax.psum(raw, axes)
        return out, jnp.zeros((), jnp.int32)

    spec_in = P(axes if len(axes) > 1 else axes[0])
    f = jax.shard_map(per_shard, mesh=mesh, in_specs=(spec_in,),
                      out_specs=(spec_in, P()), check_vma=False)
    return f(padded)


def execute_sharded(arr: jax.Array, compiled: CompiledProgram, mesh, *,
                    collect_stats: bool = False,
                    block_rows: int | None = None,
                    interpret: bool | None = None
                    ) -> tuple[jax.Array, TracedStats | None]:
    """Shard rows over the mesh's batch axes and run the fused kernel
    per-shard; traced counters are psummed so the returned stats are global.
    """
    axes = data_axes(mesh) or tuple(mesh.axis_names[:1])
    n_shards = math.prod(mesh.shape[a] for a in axes)
    rows, cols = arr.shape
    if rows == 0:                       # empty batch: skip the shard_map
        return execute(arr, compiled, collect_stats=collect_stats,
                       block_rows=block_rows, interpret=interpret)
    block_rows = block_rows or min(BLOCK_ROWS,
                                   max(8, -(-rows // n_shards)))
    padded, _ = _pad_rows(jnp.asarray(arr, jnp.int8), n_shards * block_rows)
    with trace.span("execute_sharded", cat="execute", rows=rows,
                    steps=compiled.n_steps, shards=n_shards):
        out, raw = sharded_program_run(padded, compiled.schedule_tensors,
                                       mesh, axes, rows, block_rows,
                                       collect_stats=collect_stats,
                                       interpret=interpret)
    out = out[:rows]
    if collect_stats:
        return out, TracedStats(raw)
    return out, None


# ---------------------------------------------------------------------------
# Driver-style front door (what core/ap.py routes through)
# ---------------------------------------------------------------------------

def run(arr: jax.Array, program: Program | CompiledProgram, *,
        stats: APStats | None = None, mesh=None, pool=None,
        block_rows: int | None = None,
        interpret: bool | None = None) -> jax.Array:
    """Compile (cached) + execute; optionally merge traced counters into an
    existing :class:`APStats` (one host sync, after the run completes).

    ``pool`` (an :class:`~repro.apc.pool.ArrayPool`) streams row blocks
    over a bank of bounded arrays instead of the single resident array;
    mutually exclusive with ``mesh``.
    """
    compiled = (program if isinstance(program, CompiledProgram)
                else compile_program(program))
    if pool is not None:
        if mesh is not None:
            raise ValueError("pass either mesh= or pool=, not both")
        if block_rows is not None:
            raise ValueError("block_rows only applies without pool=; the "
                             "pool's own rows govern block streaming")
        from .pool import run_pooled                # lazy: import cycle
        return run_pooled(arr, compiled, pool, stats=stats,
                          interpret=interpret)
    kw = dict(collect_stats=stats is not None, block_rows=block_rows,
              interpret=interpret)
    if mesh is not None:
        out, traced = execute_sharded(arr, compiled, mesh, **kw)
    else:
        out, traced = execute(arr, compiled, **kw)
    if stats is not None:
        accumulate(stats, traced, compiled, n_rows=arr.shape[0])
    return out
