"""Fused TAP LUT-schedule kernel: one program body, pallas or compiled XLA.

TPU adaptation of the paper's in-memory property: the MvCAM row-block is the
VMEM-resident tile and the whole compare/write pass schedule (e.g. all 20
digits x 21 passes of a 20-trit add, 441 HBM round-trips in a naive
implementation) executes against that tile with exactly ONE HBM read and ONE
HBM write per block.

Layout: digits [rows, cols] int8 in HBM, rows the parallel axis (grid dim
0), cols the operand digit columns (2p+1 for a p-digit add).  The kernel
holds a block column-major (the ``lanes`` layout), ``[cols, rows / 128,
128]`` int32: the CAM rows map onto sublanes x lanes, one column is
``rows / 1024`` vregs, and a schedule step addresses its columns by a
dynamic offset on the leading axis.  Per step it loads the C compare
columns, counts each row's mismatches per key, and for each of the W
write columns loads, blends and stores it back, in place and in order —
so a step touches O(C + W) columns whatever the block's width, and steps
with duplicate write or compare columns replay serially, as the
pass-by-pass simulator does.  The schedule is read one scalar at a time
(:class:`SlotLayout`).  On a v5e, 4096-row blocks: the 250-column MAC
tile's 9129 steps took 0.79 ms (0.087 us a step), the 20-trit add's 421
steps ~32 us of device time.

The same body runs three ways, bit-identically (digits and per-block
counters, the mismatch histogram's saturating top bin included):
Mosaic on TPU, the pallas interpreter (``interpret=True``, the default off
TPU), and a jitted-XLA harness (``interpret=False`` off TPU).  The pallas
kernel's grid is (row block, schedule chunk): the digit block stays in
VMEM across the chunks while each chunk's schedule words stream through
SMEM.
"""
from __future__ import annotations

import functools
import math
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import DONT_CARE

BLOCK_ROWS = 1024

# Schedule steps traced per loop trip (Mosaic's loop lowering unrolls only
# fully or not at all, so the steps of a trip are traced by hand).  On a
# v5e over the 250-column MAC tile at 4096 rows: 0.094 / 0.092 / 0.088 /
# 0.085 us per step at unroll 1 / 2 / 4 / 8; 4, since 8 doubles the trace
# for 3%.
UNROLL = 4


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` resolves per backend: Mosaic on TPU, the pallas interpreter
    elsewhere; ``REPRO_AP_INTERPRET`` overrides the default."""
    if interpret is not None:
        return bool(interpret)
    env = os.environ.get("REPRO_AP_INTERPRET")
    if env is not None:
        return env.lower() not in ("0", "false", "no")
    return jax.default_backend() != "tpu"


# The pallas kernel's per-block counter tile: (8, 128) int32, the traced
# counters [sets, resets, hist[0..hist_bins)] in the lanes of its row 0.
COUNT_LANES = 128
# Schedule slots per grid step along the pallas kernel's schedule axis.  The
# schedule is read as scalars from SMEM (1 MiB on v5e), one chunk per grid
# step, so program length is not bounded by it: a 9k-step MAC tile at 16
# words per slot would need 570 KiB whole, a chunk needs 64 KiB.
SCHED_CHUNK = 1024


class SlotLayout(NamedTuple):
    """Field offsets of one schedule slot in the flat int32 word stream:
    ``[cmp_cols(C) | keys(K*C) | key_valid(K) | hist_flag | wr_cols(W) |
    wr_vals(W)]``."""
    n_c: int
    n_k: int
    n_w: int

    @property
    def key(self) -> int:
        return self.n_c

    @property
    def kv(self) -> int:
        return self.n_c * (1 + self.n_k)

    @property
    def hist(self) -> int:
        return self.kv + self.n_k

    @property
    def wc(self) -> int:
        return self.hist + 1

    @property
    def wv(self) -> int:
        return self.wc + self.n_w

    @property
    def width(self) -> int:
        return self.wv + self.n_w


def _schedule_words(cmp_cols, keys, key_valid, hist_flag, wr_cols, wr_vals
                   ) -> tuple[jax.Array, SlotLayout]:
    """The 6 dense schedule tensors as one ``[S, F]`` int32 word matrix."""
    n = cmp_cols.shape[0]
    words = jnp.concatenate(
        [jnp.asarray(t).astype(jnp.int32).reshape(n, -1)
         for t in (cmp_cols, keys, key_valid, hist_flag, wr_cols, wr_vals)],
        axis=1)
    return words, SlotLayout(cmp_cols.shape[1], keys.shape[1],
                             wr_cols.shape[1])


def _noop_slots(n: int, lay: SlotLayout) -> jax.Array:
    """``n`` slots that compare nothing and write nothing (no valid key,
    every write column -1): the schedule-chunk padding the body skips."""
    row = ([-1] * lay.n_c + [0] * (lay.wc - lay.n_c) + [-1] * lay.n_w
           + [0] * lay.n_w)
    return jnp.tile(jnp.asarray(row, jnp.int32)[None, :], (n, 1))


def _replay(step, n_iter: int, unroll: int, carry):
    """``step(t, carry)`` for ``t`` in ``range(n_iter)``, ``unroll`` steps
    to a loop trip."""
    def unrolled(t, carry):
        for u in range(unroll):
            carry = step(t * unroll + u, carry)
        return carry

    carry = jax.lax.fori_loop(0, n_iter // unroll, unrolled, carry)
    for t in range(n_iter - n_iter % unroll, n_iter):
        carry = step(t, carry)
    return carry


def _lanes_block_body(load, store, state, row_ok, slot, lay: SlotLayout,
                      n_slots: int, *, collect_stats: bool, hist_bins: int):
    """Replay ``n_slots`` schedule slots on one row-block held column-major.

    ``load(state, c)`` returns digit column ``c`` (int32, rows on sublanes
    x lanes, the shape of ``row_ok``) and ``store(state, c, v)`` writes it
    back, returning the new ``state``: a VMEM ref in the pallas kernel, an
    array in the XLA harness.  ``slot(s)(off)`` is the int32 scalar at
    field ``off`` of slot ``s``.  Each slot loads its C compare columns and
    loads, blends and stores its W write columns, in place and one write
    column after another.  Counters are per-row int32 accumulators,
    reduced once at the end into the (1, 2 + hist_bins) row [sets, resets,
    hist...]; a key compares at most C columns, so bins above C stay empty
    and are not kept.  Returns ``(state, counts)``.
    """
    n_bins = min(lay.n_c, hist_bins - 1) + 1
    zero = jnp.zeros(row_ok.shape, jnp.int32)

    def step(s, carry):
        state, acc = carry
        rd = slot(s)
        cols = [rd(c) for c in range(lay.n_c)]
        sub = [load(state, jnp.maximum(c, 0)) for c in cols]
        on = [jnp.where(c >= 0, 1, 0) for c in cols]     # -1: padding
        hist_on = rd(lay.hist) != 0
        sets, resets, *hist = acc if collect_stats else (None, None)
        tag = any_kv = None
        for k in range(lay.n_k):
            kv = rd(lay.kv + k) != 0
            mm = zero
            for c in range(lay.n_c):
                key = rd(lay.key + k * lay.n_c + c)
                miss = (sub[c] != key) & (sub[c] != DONT_CARE)
                mm = mm + jnp.where(miss, on[c], 0)
            # mismatch count doubles as the matcher: full match <=> mm == 0
            hit = (mm == 0) & kv
            tag = hit if tag is None else tag | hit
            any_kv = kv if any_kv is None else any_kv | kv
            if collect_stats:
                # the top bin saturates (>= hist_bins-1 mismatches)
                counted = row_ok & (kv & hist_on)
                top = jnp.minimum(mm, hist_bins - 1)
                hist = [h + jnp.where(counted & (top == b), 1, 0)
                        for b, h in enumerate(hist)]
        tag = row_ok if tag is None else (tag | ~any_kv) & row_ok
        for w in range(lay.n_w):
            wc = rd(lay.wc + w)
            col = jnp.maximum(wc, 0)
            v = rd(lay.wv + w)
            old = load(state, col)
            changed = tag & (old != v) & (wc >= 0)
            if collect_stats:
                sets = sets + jnp.where(changed, 1, 0)
                resets = resets + jnp.where(changed & (old != DONT_CARE), 1,
                                            0)
            state = store(state, col, jnp.where(changed, v, old))
        return state, ((sets, resets, *hist) if collect_stats else ())

    acc = (zero,) * (2 + n_bins) if collect_stats else ()
    state, acc = _replay(step, n_slots, UNROLL, (state, acc))
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 2 + hist_bins), 1)
    counts = jnp.zeros_like(lane)
    for idx, a in enumerate(acc):
        counts = counts + jnp.where(
            lane == idx, jnp.sum(a, axis=(0, 1), keepdims=True), 0)
    return state, counts


def _slot_reader(words_ref, lay: SlotLayout, interpret: bool):
    """``slot(s)(off)``: the int32 word at field ``off`` of slot ``s`` of
    the SMEM schedule chunk."""
    def slot(s):
        if not interpret:                       # Mosaic: SMEM scalar loads
            return lambda off: words_ref[s * lay.width + off]
        # interpreter: one slice per slot, then static indexing
        row = words_ref[pl.ds(s * lay.width, lay.width)]
        return lambda off: row[off]
    return slot


def _tap_lanes_kernel(n_valid_ref, words_ref, arr_ref, out_ref, *refs,
                      lay: SlotLayout, chunk: int, n_chunks: int,
                      interpret: bool, collect_stats: bool, hist_bins: int):
    """Pallas body: grid (row block ``i``, schedule chunk ``j``).

    ``arr_ref``/``out_ref`` are the block's ``[block_rows, cols]`` int8
    digits.  The replay runs on a column-major int32 copy in VMEM scratch
    (the last of ``refs``), ``[cols, block_rows / lane, lane]``: chunk 0
    widens and transposes the block into it, 1024 rows at a time, and the
    last chunk transposes and narrows it back into ``out_ref``.  A column
    is then ``block_rows / 1024`` vregs at a dynamic leading-axis offset,
    so a schedule step touches only the columns it names.  Rows past
    ``n_valid`` are masked out of writes and counters, and a block holding
    none of the valid rows is skipped.  The whole block replays at once: on
    a v5e a step of the 250-column MAC tile took 0.088 us over 4096 rows,
    and 0.175 us as four 1024-row sub-blocks (0.052 us with three of them
    padding and skipped) — the scalar reads of a step dominate.
    """
    i, j = pl.program_id(0), pl.program_id(1)
    blk_ref = refs[-1]
    n_cols, nb, lane = blk_ref.shape
    block_rows = nb * lane
    # rows per transpose: 1024 (8 x 128) keeps each one a few vregs a column
    tr_rows = 1024 if block_rows % 1024 == 0 else block_rows
    tr_nb = tr_rows // lane

    def each_tile(f):
        def body(t, carry):
            f(pl.ds(pl.multiple_of(t * tr_rows, tr_rows), tr_rows),
              pl.ds(pl.multiple_of(t * tr_nb, tr_nb), tr_nb))
            return carry
        jax.lax.fori_loop(0, block_rows // tr_rows, body, 0)

    @pl.when(j == 0)
    def _init():
        def widen(rows, planes):
            blk_ref[:, planes, :] = arr_ref[rows, :].astype(
                jnp.int32).T.reshape(n_cols, tr_nb, lane)
        each_tile(widen)
        if collect_stats:
            refs[0][...] = jnp.zeros(refs[0].shape, jnp.int32)

    n_local = n_valid_ref[0] - i * block_rows

    @pl.when(n_local > 0)
    def _run():
        row = (lane * jax.lax.broadcasted_iota(jnp.int32, (nb, lane), 0)
               + jax.lax.broadcasted_iota(jnp.int32, (nb, lane), 1))

        def store(state, c, v):
            blk_ref[c] = v
            return state

        _, counts = _lanes_block_body(
            lambda state, c: blk_ref[c], store, (), row < n_local,
            _slot_reader(words_ref, lay, interpret), lay, chunk,
            collect_stats=collect_stats, hist_bins=hist_bins)
        if collect_stats:
            refs[0][0:1, :2 + hist_bins] += counts

    @pl.when(j == n_chunks - 1)
    def _store():
        def narrow(rows, planes):
            out_ref[rows, :] = blk_ref[:, planes, :].reshape(
                n_cols, tr_rows).T.astype(out_ref.dtype)
        each_tile(narrow)


def _lane_width(block_rows: int) -> int:
    """Rows per lane row of the column-major layout: the 128 lanes, or the
    whole block when it is not a multiple of them."""
    return 128 if block_rows % 128 == 0 else block_rows


def _tap_program_pallas(arr, sched, n_valid, *, block_rows: int,
                        collect_stats: bool, hist_bins: int, interpret: bool):
    """The pallas launch of a whole program (Mosaic on TPU, or the pallas
    interpreter).  ``sched`` is the 6 dense schedule tensors, ``n_valid``
    the int32 count of real rows; returns ``(out, counts)`` with ``counts``
    the per-grid-block (grid, 2 + hist_bins) counters, or None.

    Kept free of backend probing so a test can lower it for a described
    TPU that is not attached."""
    rows, cols = arr.shape
    if rows % block_rows:
        raise ValueError(f"rows={rows} not a multiple of {block_rows}")
    if 2 + hist_bins > COUNT_LANES:
        raise ValueError(f"hist_bins={hist_bins} exceeds the counter row")
    words, lay = _schedule_words(*sched)
    n_slots = words.shape[0]
    n_chunks = -(-n_slots // SCHED_CHUNK)
    chunk = n_slots
    if n_chunks > 1:
        # a partial SMEM block must span a multiple of the 1024-word SMEM
        # tiling
        m = 1024 // math.gcd(lay.width, 1024)
        chunk = m * -(-n_slots // (n_chunks * m))
    if n_chunks * chunk > n_slots:
        words = jnp.concatenate(
            [words, _noop_slots(n_chunks * chunk - n_slots, lay)])
    grid = (rows // block_rows, n_chunks)
    # rows onto sublanes x lanes: one [block_rows / lane, lane] plane per
    # column
    lane = _lane_width(block_rows)
    kernel = functools.partial(
        _tap_lanes_kernel, lay=lay, chunk=chunk, n_chunks=n_chunks,
        interpret=interpret, collect_stats=collect_stats,
        hist_bins=hist_bins)
    digits = pl.BlockSpec((block_rows, cols), lambda i, j: (i, 0))
    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((chunk * lay.width,), lambda i, j: (j,),
                             memory_space=pltpu.SMEM),
                digits]
    out_specs = [digits]
    out_shape = [jax.ShapeDtypeStruct((rows, cols), jnp.int8)]
    if collect_stats:
        out_specs.append(pl.BlockSpec((8, COUNT_LANES), lambda i, j: (i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((grid[0] * 8, COUNT_LANES),
                                              jnp.int32))
    res = pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((cols, block_rows // lane, lane),
                                   jnp.int32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(jnp.reshape(n_valid, (1,)).astype(jnp.int32), words.reshape(-1),
      jnp.asarray(arr, jnp.int8))
    if not collect_stats:
        return res[0], None
    counts = res[1].reshape(grid[0], 8, COUNT_LANES)[:, 0, :2 + hist_bins]
    return res[0], counts


def _tap_program_xla(padded, sched, n_valid, *, block_rows: int,
                     collect_stats: bool, hist_bins: int):
    """Compiled-XLA path: the same step body mapped over row-blocks.

    Used when ``interpret=False`` on a non-TPU backend, where pallas has no
    compiled lowering — the body is ordinary static vector algebra, so
    plain jit gives the compiled semantics (and per-block counter layout)
    the TPU kernel has, bit-identically.
    """
    rows, cols = padded.shape
    grid = rows // block_rows
    words, lay = _schedule_words(*sched)
    lane = _lane_width(block_rows)
    shape = (block_rows // lane, lane)

    def slot(s):
        row = jax.lax.dynamic_index_in_dim(words, s, keepdims=False)
        return lambda off: row[off]

    def per_block(args):
        i, blk = args
        row = (i * block_rows
               + lane * jax.lax.broadcasted_iota(jnp.int32, shape, 0)
               + jax.lax.broadcasted_iota(jnp.int32, shape, 1))
        planes, counts = _lanes_block_body(
            lambda st, c: jax.lax.dynamic_index_in_dim(st, c,
                                                       keepdims=False),
            lambda st, c, v: jax.lax.dynamic_update_index_in_dim(
                st, v[None], c, 0),
            blk.astype(jnp.int32).T.reshape(cols, *shape),
            row < n_valid, slot, lay, words.shape[0],
            collect_stats=collect_stats, hist_bins=hist_bins)
        out = planes.reshape(cols, block_rows).T.astype(jnp.int8)
        return out, counts[0]

    # sequential lax.map over row-blocks, mirroring the pallas grid
    out, counts = jax.lax.map(
        per_block, (jnp.arange(grid, dtype=jnp.int32),
                    padded.reshape(grid, block_rows, cols)))
    return out.reshape(rows, cols), counts


def tap_run_program(arr: jax.Array, cmp_cols: jax.Array, keys: jax.Array,
                    key_valid: jax.Array, hist_flag: jax.Array,
                    wr_cols: jax.Array, wr_vals: jax.Array,
                    n_valid_rows: jax.Array, *,
                    block_rows: int = BLOCK_ROWS,
                    collect_stats: bool = False, hist_bins: int = 8,
                    interpret: bool | None = None):
    """Run a whole compiled program: one launch, grid over row-blocks.

    Returns ``out`` (same shape as ``arr``) and, when ``collect_stats``, a
    per-grid-block (grid, 2 + hist_bins) int32 counter tensor laid out as
    [sets, resets, hist[0..hist_bins)] — summed over grid by the caller
    (still in-graph).  The schedule tensors
    (:attr:`repro.apc.lower.CompiledProgram.schedule_tensors`) are runtime
    args, so one compiled kernel serves every program with the same dense
    shape.  ``interpret=None`` resolves per backend (see
    :func:`resolve_interpret`); ``interpret=False`` off-TPU runs the
    jitted XLA harness — same body, same per-block counter layout,
    bit-identical.
    """
    # env-default resolution happens OUT here, before the jit boundary —
    # inside it the resolved value would be baked into the cache entry
    # keyed on the None static and never re-read on cache hits
    return _tap_run_program_jit(
        arr, cmp_cols, keys, key_valid, hist_flag, wr_cols, wr_vals,
        n_valid_rows, block_rows=block_rows, collect_stats=collect_stats,
        hist_bins=hist_bins, interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=(
    "block_rows", "collect_stats", "hist_bins", "interpret"))
def _tap_run_program_jit(arr, cmp_cols, keys, key_valid, hist_flag,
                         wr_cols, wr_vals, n_valid_rows, *, block_rows: int,
                         collect_stats: bool, hist_bins: int,
                         interpret: bool):
    rows = arr.shape[0]
    if rows % block_rows:
        raise ValueError(f"rows={rows} not a multiple of {block_rows}")
    sched = (cmp_cols, keys, key_valid, hist_flag, wr_cols, wr_vals)
    n_valid = jnp.asarray(n_valid_rows, jnp.int32)
    body_kw = dict(block_rows=block_rows, collect_stats=collect_stats,
                   hist_bins=hist_bins)
    if not interpret and jax.default_backend() != "tpu":
        return _tap_program_xla(jnp.asarray(arr, jnp.int8), sched, n_valid,
                                **body_kw)
    return _tap_program_pallas(arr, sched, n_valid, interpret=interpret,
                               **body_kw)
