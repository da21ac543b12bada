"""Public jit'd wrappers around the fused TAP LUT kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core.lut import LUT
from .kernel import (BLOCK_ROWS, resolve_interpret, tap_apply_schedule,
                     tap_run_program)
from .ref import ripple_add_schedule, schedule_from_lut

# Schedules longer than this run through the packed fori_loop program kernel
# (tap_run_program) instead of unrolling every pass into the trace: a 20-trit
# non-blocked add is 421 steps, and the unrolled trace costs minutes to
# build/compile per (schedule, shape) while the packed kernel traces one
# generic step.  Short schedules keep the unrolled path (no gather overhead)
# when interpreted.
UNROLL_STEP_LIMIT = 64


def _pad_rows(arr: jax.Array, block_rows: int) -> tuple[jax.Array, int]:
    rows = arr.shape[0]
    padded = (rows + block_rows - 1) // block_rows * block_rows
    if padded != rows:
        # don't-care rows never match any key and pass through unchanged
        pad = jnp.full((padded - rows, arr.shape[1]), -1, dtype=arr.dtype)
        arr = jnp.concatenate([arr, pad], axis=0)
    return arr, rows


def _run_schedule(arr: jax.Array, sched, block_rows: int,
                  interpret: bool | None,
                  kernel_variant: str | None = None) -> jax.Array:
    """Dispatch a flat schedule to the unrolled or fori_loop kernel."""
    padded, rows = _pad_rows(arr, block_rows)
    # The unrolled kernel runs on the pallas interpreter only; a compiled
    # run (Mosaic on TPU, the jitted-XLA harness elsewhere) takes the
    # program kernel, which compiles on both.
    if len(sched) <= UNROLL_STEP_LIMIT and resolve_interpret(interpret):
        out = tap_apply_schedule(padded, sched, block_rows=block_rows)
        return out[:rows]
    from ...apc.lower import (Step, _compile_steps,     # lazy: import cycle
                              resolve_schedule)
    compiled = _compile_steps(tuple(
        Step(keys=k, compare_cols=c, write_cols=w, write_vals=v,
             in_hist=bool(k)) for k, c, w, v in sched))
    tensors, variant, pack, _ = resolve_schedule(compiled, kernel_variant)
    out, _ = tap_run_program(
        padded, *tensors, jnp.int32(rows), block_rows=block_rows,
        interpret=interpret, variant=variant, pack=pack)
    return out[:rows]


def tap_apply_lut(arr: jax.Array, lut: LUT, col_map: tuple[int, ...],
                  block_rows: int = BLOCK_ROWS,
                  interpret: bool | None = None,
                  kernel_variant: str | None = None) -> jax.Array:
    """One LUT application (single digit position) on the kernel path."""
    sched = schedule_from_lut(lut, col_map)
    return _run_schedule(arr, sched, block_rows, interpret, kernel_variant)


def tap_ripple_add(arr: jax.Array, lut: LUT, width: int, carry_col: int,
                   a_base: int = 0, b_base: int | None = None,
                   block_rows: int = BLOCK_ROWS,
                   interpret: bool | None = None,
                   kernel_variant: str | None = None) -> jax.Array:
    """Fused p-digit in-place add: B <- A + B in ONE kernel launch.

    This is the flagship fusion: a 20-trit non-blocked add is 441 compare +
    441 write passes; the naive path moves the array to/from HBM for each,
    while this launch streams each row-block through VMEM exactly once.
    Wide adds route through the packed fori_loop program kernel (see
    ``UNROLL_STEP_LIMIT``) so trace time stays O(1) in width.
    """
    sched = ripple_add_schedule(lut, width, carry_col, a_base, b_base)
    return _run_schedule(arr, sched, block_rows, interpret, kernel_variant)


def hbm_traffic_model(n_rows: int, n_cols: int, lut: LUT, width: int
                      ) -> dict[str, float]:
    """Analytical HBM bytes: fused kernel vs per-pass naive replay.

    The per-pass path reads the compare columns and rewrites the write
    columns for every pass; the fused path reads + writes the array once.
    Used by benchmarks/kernels_bench.py for the roofline argument.
    """
    bytes_array = n_rows * n_cols                       # int8
    naive = 0
    for blk in lut.blocks:
        naive += len(blk.keys) * n_rows * lut.width     # compare reads
        naive += n_rows * len(blk.write_cols) * 2       # write read+write
    naive *= width                                      # per digit position
    fused = 2 * bytes_array                             # one read + one write
    # n_rows == 0 moves no bytes either way: report no reduction (1x)
    return {"naive_bytes": float(naive), "fused_bytes": float(fused),
            "reduction_x": naive / fused if fused else 1.0}
