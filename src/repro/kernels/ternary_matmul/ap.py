"""AP backend for the packed-ternary matmul (impl="ap").

Runs the whole M x N output tile as associative-processor MAC programs:
row (m, n) of the MvCAM bank holds activation vector x[m, :] as radix-r
digit groups, weight column w[:, n] as trit digits, and an accumulator;
:func:`repro.apc.compile_mac` compiles the K-term predicated add/subtract
schedule once per (radix, K, width) and the executor replays it with one
pallas_call per row-block.

Column-budget / partial-sum model: a single MvCAM array has a bounded
column count, and the untiled MAC row needs ``K*(width+1) + width + 1``
columns — serving-scale K does not fit one array.  Passing ``pool=`` (an
:class:`repro.apc.ArrayPool`) or ``k_tile=`` routes the matmul through
:func:`repro.apc.compile_mac_tiled`: the reduction axis splits into
K-tiles, each tile an ordinary MAC program producing a radix-complement
partial accumulator at the same width, and a ripple-add reduction chain
(itself within the column budget) folds the partials.  Because every
program wraps mod ``r^width``, the tiled digits — and hence the decoded
matmul — are bit-identical to the untiled program, and the charged
compare/write cycles are the exact sum of the tile programs plus the
reduction programs.  Row blocks stream over the pool's arrays
double-buffered (block *b* on array *b mod n_arrays*), the bank-level
parallelism of the in-memory-computing literature.

Data movement: encode (digit extraction, weight trits, row replication)
and decode (signed radix-complement) are pure ``jnp`` on device — no
``[M*N, K']`` host materialization; the one host device sync is the
integer-validation/width reduction on the [M, K] input (two scalars), and
results stay on device until the caller converts.

This is the paper's in-memory arithmetic applied to serving: no multiplier,
no MXU — compare/write cycles only, with the functional-simulator counters
(write cycles -> Table XI energy) available per matmul.  It is exact
integer arithmetic, so activations must be integer-valued (quantized
activations, integer token counts, ...); for float activations use the
packed Pallas kernel.  Useful today as a bit-exact cross-check of the
packed kernel and as the cost model for an AP accelerator running the
serving path; wall-clock on a TPU/CPU host it loses to the MXU-backed
kernel by design.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .ref import unpack_ternary

__all__ = ["ternary_matmul_ap", "ap_matmul_cycle_counts", "default_k_tile"]


@jax.jit
def _int_check(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """One fused reduction: (all entries integer-valued?, max |x|)."""
    xf = jnp.asarray(x, jnp.float32)
    ok = jnp.all(xf == jnp.round(xf))
    return ok, jnp.max(jnp.abs(xf), initial=0.0)


def _as_int_activations(x: jax.Array) -> tuple[jax.Array, int]:
    """Validate + convert to device int32; returns (xi, max_abs).

    The ONE input-side host sync: two scalars (validity flag, |x| max) —
    the [M, K] digits themselves never round-trip.
    """
    ok, max_abs = _int_check(x)
    if not bool(ok):
        raise ValueError(
            "impl='ap' runs exact integer AP arithmetic: activations must "
            "be integer-valued (got non-integer entries); quantize x first "
            "or use impl='pallas'")
    return jnp.asarray(x, jnp.float32).astype(jnp.int32), int(max_abs)


def default_k_tile(cols: int, width: int) -> int:
    """Largest K-tile whose MAC row fits a ``cols``-column array:
    ``mac_layout(k, width).n_cols = k*(width+1) + width + 1 <= cols``."""
    kt = (cols - width - 1) // (width + 1)
    if kt < 1:
        raise ValueError(
            f"column budget {cols} cannot hold even a 1-term width-{width} "
            f"MAC row ({2 * width + 2} columns needed)")
    return kt


def ternary_matmul_ap(x: jax.Array, packed: jax.Array, scale: jax.Array,
                      *, radix: int = 3, width: int | None = None,
                      mesh=None, pool=None, runtime=None,
                      k_tile: int | None = None,
                      stats=None, block_rows: int | None = None,
                      blocked: bool = False,
                      interpret: bool | None = None,
                      kernel_variant: str | None = None,
                      unroll: int | None = None) -> jax.Array:
    """y[M, N] = (x @ unpack(packed)) * scale on the AP program executor.

    ``x`` [M, K] integer-valued; ``packed``/``scale`` as produced by
    :func:`~repro.kernels.ternary_matmul.ops.quantize_and_pack`.  ``width``
    (accumulator digits) defaults to the minimal exact width for the
    observed activation range and is VALIDATED against it when passed —
    a too-narrow accumulator would silently wrap mod ``r^width``, so it
    raises instead.  ``stats`` (an :class:`~repro.core.ap.APStats`)
    collects the functional-simulator counters for the energy model.

    Execution routing: ``pool=`` (an :class:`repro.apc.ArrayPool`) streams
    the M*N rows through the array bank, K-tiling the MAC to the pool's
    column budget (``k_tile`` overrides the derived tile; it must fit);
    ``runtime=`` (an :class:`repro.apc.Runtime`) builds the tiled MAC as a
    :class:`repro.apc.ProgramGraph` and schedules it over the runtime's
    (possibly device-spanning) bank — same digits, same counters, plus the
    graph makespan in ``runtime.last_report``; ``k_tile`` alone runs the
    tiled programs on the single-array executor (the tiled-vs-untiled
    oracle); ``mesh`` shards the M*N row axis.  ``kernel_variant`` /
    ``interpret`` / ``unroll`` pick the program-kernel formulation
    (gather / one-hot / one-hot+packed / lanes, interpreted or compiled; see
    :mod:`repro.apc.exec`) and default to the measured backend best —
    every combination is bit-exact.  Bit-exact vs
    :func:`~repro.kernels.ternary_matmul.ref.ternary_matmul_ref` on every
    route because the integer accumulator converts to float32 exactly and
    the final scale-multiply is the same float32 op.
    """
    from repro import apc
    from repro.apc import trace

    xi, max_abs = _as_int_activations(x)
    m, kdim = xi.shape
    w_ter = unpack_ternary(packed, dtype=jnp.int8)                 # [K', N]
    kp, n = w_ter.shape
    if kdim > kp:
        raise ValueError(f"x K={kdim} exceeds packed K'={kp}")
    if kdim < kp:                        # pack-time padding rows: w == 0 there
        xi = jnp.pad(xi, ((0, 0), (0, kp - kdim)))
    req_width = apc.mac_acc_width(radix, kp, max_abs)
    if width is None:
        width = req_width
    elif width < req_width:
        raise ValueError(
            f"width={width} accumulator digits wrap mod {radix}**{width} "
            f"for activations with |x| <= {max_abs} at K={kp}: exact "
            f"signed decode needs width >= {req_width} "
            f"(mac_acc_width({radix}, {kp}, {max_abs}))")
    # row (m, n) <- (x[m, :], w[:, n]): M*N dot products, device-side
    x_rows, w_rows = apc.matmul_mac_rows(xi, w_ter)                # [M*N, K']
    route = ("runtime" if runtime is not None
             else "tiled" if pool is not None or k_tile is not None
             else "plain")
    with trace.span("ternary_matmul_ap", cat="matmul", m=m, k=kp, n=n,
                    width=width, route=route):
        acc = _run_routed(apc, x_rows, w_rows, radix, kp, width,
                          mesh=mesh, pool=pool, runtime=runtime,
                          k_tile=k_tile, stats=stats, block_rows=block_rows,
                          blocked=blocked, interpret=interpret,
                          kernel_variant=kernel_variant, unroll=unroll)
    y = (acc.reshape(m, n).astype(jnp.float32)
         * jnp.asarray(scale, jnp.float32)[None, :])
    return y.astype(x.dtype)


def _run_routed(apc, x_rows, w_rows, radix, kp, width, *, mesh, pool,
                runtime, k_tile, stats, block_rows, blocked, interpret,
                kernel_variant, unroll):
    if runtime is not None:
        if mesh is not None or pool is not None:
            raise ValueError("runtime= already carries a pool; pass one of "
                             "mesh=, pool=, or runtime=")
        if block_rows is not None:
            raise ValueError("block_rows only applies without runtime=; "
                             "the runtime pool's own rows govern blocks")
        runtime.check_knobs(interpret=interpret,
                            kernel_variant=kernel_variant, unroll=unroll)
        max_cols = runtime.pool.cols
        kt = k_tile if k_tile is not None else default_k_tile(max_cols,
                                                              width)
        tiled = apc.compile_mac_tiled(radix, kp, width, kt,
                                      blocked=blocked, max_cols=max_cols)
        (digits,) = runtime.run_mac_graph([(x_rows, w_rows, tiled)],
                                          stats=stats)
        return apc.decode_signed_digits_jnp(digits, radix)
    if pool is not None or k_tile is not None:
        if mesh is not None:
            raise ValueError("the tiled/pool route does not mesh-shard; "
                             "pass one of mesh= or pool=/k_tile=")
        max_cols = pool.cols if pool is not None else None
        kt = k_tile if k_tile is not None else default_k_tile(pool.cols,
                                                              width)
        tiled = apc.compile_mac_tiled(radix, kp, width, kt,
                                      blocked=blocked, max_cols=max_cols)
        return apc.run_mac_tiled(x_rows, w_rows, tiled, pool=pool,
                                 stats=stats, block_rows=block_rows,
                                 interpret=interpret,
                                 kernel_variant=kernel_variant,
                                 unroll=unroll)
    compiled = apc.compile_mac(radix, kp, width, blocked=blocked)
    arr = apc.encode_mac_rows_jnp(x_rows, w_rows, radix, width)
    out = apc.run(arr, compiled, stats=stats, mesh=mesh,
                  block_rows=block_rows, interpret=interpret,
                  kernel_variant=kernel_variant, unroll=unroll)
    return apc.decode_mac_acc_jnp(out, radix, kp, width)           # [M*N]


def ap_matmul_cycle_counts(radix: int, K: int, width: int,
                           blocked: bool = False,
                           k_tile: int | None = None) -> dict[str, int]:
    """Schedule-static AP cycle counts for one (any-size) matmul tile.

    All M*N dot products run row-parallel, so these are the counts of the
    whole matmul, not per output — the write-cycle number the Table XI
    energy model charges at 2 ns / cycle.  With ``k_tile`` the counts are
    the exact sum of the per-tile partial-sum programs plus the ripple-add
    reduction chain (the tiled route's charges).
    """
    from repro import apc
    if k_tile is not None:
        tiled = apc.compile_mac_tiled(radix, K, width, k_tile,
                                      blocked=blocked)
        return {"compare_cycles": tiled.n_compare_cycles,
                "write_cycles": tiled.n_write_cycles,
                "steps": sum(p.n_steps for p in
                             tiled.programs + tiled.reduce_programs),
                "acc_width": width, "n_tiles": len(tiled.tiles)}
    compiled = apc.compile_mac(radix, K, width, blocked=blocked)
    return {"compare_cycles": compiled.n_compare_cycles,
            "write_cycles": compiled.n_write_cycles,
            "steps": compiled.n_steps, "acc_width": width}
