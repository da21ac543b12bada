"""Fused TAP LUT-schedule kernel: pallas + compiled-XLA program executors.

TPU adaptation of the paper's in-memory property: the MvCAM row-block is the
VMEM-resident tile and the whole compare/write pass schedule (e.g. all 20
digits x 21 passes of a 20-trit add, 441 HBM round-trips in a naive
implementation) executes against that tile with exactly ONE HBM read and ONE
HBM write per block.

Layout: digits [rows, cols] int8 in HBM, rows the parallel axis (grid dim
0), cols the operand digit columns (2p+1 for a p-digit add).  On TPU the
``lanes`` body holds a block column-major, ``[cols, rows / 128, 128]``: the
CAM rows map onto sublanes x lanes, one column is ``rows / 1024`` int32
vregs, and a schedule step addresses its columns by a dynamic offset on the
leading axis.  The other bodies keep ``[rows, cols]`` with columns on the
lanes.

Three step-body formulations for the whole-program executor, all reading
the schedule one scalar at a time (:class:`SlotLayout`):

- ``variant="lanes"`` — the TPU body: per step, load the C compare
  columns, count each row's mismatches per key, and for each of the W
  write columns load, blend and store it back, in place and in order.  A
  step touches O(C + W) columns whatever the block's width.  The kernel
  widens and transposes the [rows, cols] block into int32 VMEM scratch on
  its first schedule chunk, and back on its last.  On a v5e, 4096-row
  blocks: the 250-column MAC tile's 9129 steps took 0.79 ms (0.087 us a
  step) against 65.9 ms for the one-hot body, and the 20-trit add's 421
  steps ~32 us of device time against ~2.1 ms.
- ``variant="gather"`` — the original body: per-step dynamic column reads
  for the compare and a serial ``dynamic_update_index_in_dim`` chain for
  the writes, the lanes body's semantics with columns on the lanes.  Runs
  in the pallas interpreter and the jitted-XLA harness; it has no Mosaic
  lowering (dynamic cross-lane indexing), so a compiled run on TPU refuses
  it.
- ``variant="onehot"`` — the compare columns and key expand into a one-hot
  mask/value plane over the full row, the compare is a masked equality
  reduction, and each write a ``jnp.where(col_mask & tag, vals, block)``
  blend: O(rows x cols) per step.  No dynamic indexing and only rank-2
  temporaries, so it compiles under Mosaic too; it stays as a cross-check.
  With ``pack > 1`` each fori_loop iteration replays a whole VLIW group of
  mutually independent slots (see :class:`repro.apc.lower.PackedProgram`)
  against the pre-group block and lands all writes in one blend.

All formulations are bit-identical — digits AND traced counters, including
the mismatch histogram's saturating top bin (pinned by tests/test_pack.py).

The pallas kernel's grid is (row block, schedule chunk): the digit block
stays in VMEM across the chunks while each chunk's schedule words stream
through SMEM.
"""
from __future__ import annotations

import functools
import math
import operator
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import DONT_CARE, Step

BLOCK_ROWS = 1024

VARIANTS = ("gather", "onehot", "lanes")

# Measured defaults for the knobs the executors thread through (None = "use
# the measured default"; REPRO_AP_INTERPRET / REPRO_AP_UNROLL override for
# CI/bench sweeps).  interpret=None resolves per backend: on TPU the
# compiled path (Mosaic); on CPU/GPU hosts the pallas interpreter, which
# under jit stages to the same XLA ops and measured FASTER than the lax.map
# harness for the gather body (bench_ap_kernel records the matrix).
# interpret=False off-TPU runs the jitted-XLA harness below — the compiled
# path CI keeps green.
#
# Unroll (bench_ap_kernel, CPU host, 65k rows): the gather body is cheap
# per step and profits from unroll=4; the one-hot body is ~n_cols/C times
# fatter (full-row compares and blends), so deeper unrolls only grow the
# trace — unroll=2 flat / 1 packed measured fastest.  The lanes body, on a
# v5e over the 250-column MAC tile at 4096 rows: 0.094 / 0.092 / 0.088 /
# 0.085 us per step at unroll 1 / 2 / 4 / 8; 4, since 8 doubles the trace
# for 3%.
DEFAULT_UNROLL = {"gather": 4, "onehot": 2, "onehot_packed": 1,
                  "lanes": 4}


def resolve_interpret(interpret: bool | None) -> bool:
    if interpret is not None:
        return bool(interpret)
    env = os.environ.get("REPRO_AP_INTERPRET")
    if env is not None:
        return env.lower() not in ("0", "false", "no")
    return jax.default_backend() != "tpu"


def resolve_unroll(unroll: int | None, variant: str, pack: int) -> int:
    if unroll is None and os.environ.get("REPRO_AP_UNROLL"):
        unroll = int(os.environ["REPRO_AP_UNROLL"])
    if unroll is not None:
        if unroll < 1:
            raise ValueError(f"unroll must be >= 1, got {unroll}")
        return int(unroll)
    key = "onehot_packed" if (variant == "onehot" and pack > 1) else variant
    return DEFAULT_UNROLL[key]


def _tap_kernel(arr_ref, out_ref, *, schedule: tuple[Step, ...]):
    """Kernel body: replay the static schedule on the resident block."""
    block = arr_ref[...]                              # [block_rows, cols] int8
    rows = block.shape[0]
    for keys, ccols, wcols, wvals in schedule:
        if not keys:                                  # unconditional write
            tag = jnp.ones((rows,), dtype=jnp.bool_)
        else:
            tag = jnp.zeros((rows,), dtype=jnp.bool_)
            for key in keys:
                m = jnp.ones((rows,), dtype=jnp.bool_)
                for c, k in zip(ccols, key):
                    cell = block[:, c]
                    m &= (cell == k) | (cell == DONT_CARE)
                tag |= m
        cols_out = []
        wmap = dict(zip(wcols, wvals))
        for c in range(block.shape[1]):
            if c in wmap:
                cols_out.append(
                    jnp.where(tag, jnp.int8(wmap[c]), block[:, c]))
            else:
                cols_out.append(block[:, c])
        block = jnp.stack(cols_out, axis=1)
    out_ref[...] = block


# ---------------------------------------------------------------------------
# Whole-program step body (shared by the pallas kernel and the XLA path)
# ---------------------------------------------------------------------------

# The pallas kernel's per-block counter tile: (8, 128) int32, the traced
# counters [sets, resets, hist[0..hist_bins)] in the lanes of its row 0.
COUNT_LANES = 128
# Schedule slots per grid step along the pallas kernel's schedule axis.  The
# schedule is read as scalars from SMEM (1 MiB on v5e), one chunk per grid
# step, so program length is not bounded by it: a 9k-step MAC tile at 16
# words per slot would need 570 KiB whole, a chunk needs 64 KiB.
SCHED_CHUNK = 1024
# Rows per inner sub-block of the compiled one-hot kernel (columns on the
# lanes): each sub-block replays the whole schedule chunk with its digits
# as the loop carry, and sub-blocks holding only padding rows are skipped.
# On a v5e, a 9129-step MAC tile over 4096 x 250 digits with counters took
# 10.6 / 9.3 / 9.0 / 9.1 us per step at 256 / 512 / 1024 / 4096 rows.  The
# lanes body replays its whole block at once (see _tap_lanes_kernel).
TPU_SUB_ROWS = 1024


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
    every write column -1): the schedule padding every body skips."""
    row = ([-1] * lay.n_c + [0] * (lay.wc - lay.n_c) + [-1] * lay.n_w
           + [0] * lay.n_w)
    return jnp.tile(jnp.asarray(row, jnp.int32)[None, :], (n, 1))


def _count(mask):
    """Set entries of a rank-2 mask, as a (1, 1) int32."""
    return jnp.sum(jnp.where(mask, 1, 0), axis=(0, 1), keepdims=True)


def _program_block_body(block, row_ok, slot, lay: SlotLayout, n_slots: int, *,
                        collect_stats: bool, hist_bins: int, unroll: int,
                        variant: str, pack: int):
    """Replay ``n_slots`` schedule slots on one resident row-block.

    ``block`` [rows, cols] digits, ``row_ok`` [rows, 1] bool (padding rows
    masked out of writes and counters), ``slot(s)`` a reader of slot ``s``:
    ``slot(s)(off)`` is the int32 scalar at field ``off`` (see
    :class:`SlotLayout`).  Every temporary is rank 2 and the schedule is
    read one scalar at a time, so the same body runs under Mosaic, the
    pallas interpreter and plain XLA.  Returns ``(out_block, counts)`` with
    ``counts`` the (1, 2 + hist_bins) int32 counter row [sets, resets,
    hist...] (zeros when ``collect_stats`` is off).
    """
    rows, n_cols = block.shape
    dt = block.dtype
    if n_slots % pack:
        raise ValueError(f"{n_slots} schedule slots not a multiple of "
                         f"pack={pack}")
    col_iota = jax.lax.broadcasted_iota(jnp.int32, (1, n_cols), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 2 + hist_bins), 1)

    def bump(counts, idx, n):
        return counts + jnp.where(lane == idx, n, 0)

    def slot_compare(blk, rd, counts):
        """Tag column (+ histogram update) for the slot ``rd`` reads."""
        cols = [rd(c) for c in range(lay.n_c)]
        if variant == "gather":
            sub = [jax.lax.dynamic_index_in_dim(blk, jnp.maximum(c, 0),
                                                axis=1) for c in cols]
        else:
            # one-hot formulation: expand the compare columns + key into a
            # full-row mask/value plane (pad columns -1 never hit the iota;
            # compare columns are distinct, enforced by resolve_schedule)
            # — the compare itself is then a masked equality reduction over
            # whole rows, the AP's "broadcast key to every cell" with zero
            # dynamic indexing
            hits = [col_iota == c for c in cols]
            cmp_mask = functools.reduce(operator.or_, hits)
        hist_on = rd(lay.hist) != 0
        kvs = []
        tag = None
        for k in range(lay.n_k):
            kv = rd(lay.kv + k) != 0
            kvs.append(kv)
            key = [rd(lay.key + k * lay.n_c + c) for c in range(lay.n_c)]
            if variant == "gather":
                mm = sum(jnp.where((sub[c] != key[c].astype(dt))
                                   & (sub[c] != DONT_CARE) & (cols[c] >= 0),
                                   1, 0) for c in range(lay.n_c))
            else:
                key_row = sum(jnp.where(hits[c], key[c], 0)
                              for c in range(lay.n_c)).astype(dt)
                miss = (blk != key_row) & (blk != DONT_CARE) & cmp_mask
                mm = jnp.sum(jnp.where(miss, 1, 0), axis=1, keepdims=True)
            # mismatch count doubles as the matcher: full match <=> mm == 0
            hit = (mm == 0) & kv
            tag = hit if tag is None else tag | hit
            if collect_stats:
                # one lane per histogram bin: row r lands in lane
                # 2 + min(mm, hist_bins - 1) — the top bin saturates
                # (>= hist_bins-1 mismatches) instead of dropping mass
                counted = row_ok & (kv & hist_on)
                in_bin = (2 + jnp.minimum(mm, hist_bins - 1) == lane) \
                    & counted
                counts = counts + jnp.sum(jnp.where(in_bin, 1, 0), axis=0,
                                          keepdims=True)
        any_kv = functools.reduce(operator.or_, kvs)
        return (tag | ~any_kv) & row_ok, counts

    def step_gather(s, carry):
        block, counts = carry
        rd = slot(s)
        tag, counts = slot_compare(block, rd, counts)
        for w in range(lay.n_w):
            wc = rd(lay.wc + w)
            col = jnp.maximum(wc, 0)
            v = rd(lay.wv + w).astype(dt)
            old = jax.lax.dynamic_index_in_dim(block, col, axis=1)
            changed = tag & (old != v) & (wc >= 0)
            if collect_stats:
                counts = bump(counts, 0, _count(changed))
                counts = bump(counts, 1,
                              _count(changed & (old != DONT_CARE)))
            block = jax.lax.dynamic_update_index_in_dim(
                block, jnp.where(changed, v, old), col, axis=1)
        return block, counts

    def step_onehot(g, carry):
        block, counts = carry
        # all slots of the group compare against (and count set/reset deltas
        # vs) the pre-group block; the pack pass guarantees slots are
        # mutually independent, so the single combined blend below equals
        # serial application slot by slot — bit-exactly, counters included
        apply = None
        gval = None
        for p in range(pack):
            rd = slot(g * pack + p)
            tag, counts = slot_compare(block, rd, counts)
            w_hits = [col_iota == rd(lay.wc + w) for w in range(lay.n_w)]
            wmask = functools.reduce(operator.or_, w_hits)
            wval = sum(jnp.where(w_hits[w], rd(lay.wv + w), 0)
                       for w in range(lay.n_w))
            slot_apply = tag & wmask
            if collect_stats:
                changed = slot_apply & (block != wval.astype(dt))
                counts = bump(counts, 0, _count(changed))
                counts = bump(counts, 1,
                              _count(changed & (block != DONT_CARE)))
            apply = slot_apply if apply is None else apply | slot_apply
            gval = wval if gval is None else gval + wval   # disjoint cols
        block = jnp.where(apply, gval.astype(dt), block)
        return block, counts

    step = step_gather if variant == "gather" else step_onehot
    return _replay(step, n_slots // pack, unroll,
                   (block, jnp.zeros_like(lane)))


def _replay(step, n_iter: int, unroll: int, carry):
    """``step(t, carry)`` for ``t`` in ``range(n_iter)``, ``unroll`` steps
    to a loop trip: Mosaic's loop lowering unrolls only fully or not at
    all, so the steps of a trip are traced by hand."""
    def unrolled(t, carry):
        for u in range(unroll):
            carry = step(t * unroll + u, carry)
        return carry

    carry = jax.lax.fori_loop(0, n_iter // unroll, unrolled, carry)
    for t in range(n_iter - n_iter % unroll, n_iter):
        carry = step(t, carry)
    return carry


def _lanes_block_body(load, store, state, row_ok, slot, lay: SlotLayout,
                      n_slots: int, *, collect_stats: bool, hist_bins: int,
                      unroll: int):
    """Replay ``n_slots`` schedule slots on one row-block held column-major.

    ``load(state, c)`` returns digit column ``c`` (int32, rows on sublanes
    x lanes, the shape of ``row_ok``) and ``store(state, c, v)`` writes it
    back, returning the new ``state``: a VMEM ref in the pallas kernel, an
    array in the XLA harness.  Each slot loads its C compare columns and
    loads, blends and stores its W write columns, in place and one write
    column after another as the gather body does, so a step costs O(C + W)
    column loads whatever the block's width, and a packed group replays
    slot by slot (``pack_steps`` makes that equal the group replay).
    Counters are per-row int32 accumulators, reduced once at the end into
    the (1, 2 + hist_bins) row [sets, resets, hist...]; a key compares at
    most C columns, so bins above C stay empty and are not kept.  Returns
    ``(state, counts)``.
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
    state, acc = _replay(step, n_slots, unroll, (state, acc))
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


def _tap_program_kernel(n_valid_ref, words_ref, arr_ref, out_ref,
                        *stats_refs, lay: SlotLayout, chunk: int,
                        block_rows: int, sub_rows: int, interpret: bool,
                        collect_stats: bool, hist_bins: int, unroll: int,
                        variant: str, pack: int):
    """Pallas body: grid (row block ``i``, schedule chunk ``j``).

    The output block stays resident across ``j`` (its index ignores ``j``):
    chunk 0 copies the input digits in, every chunk replays its ``chunk``
    slots — read as scalars from the SMEM chunk — on each ``sub_rows`` row
    sub-block in turn, and the counters accumulate into row 0 of the block's
    (8, COUNT_LANES) stats tile.  Unlike :func:`_tap_kernel` (schedule
    unrolled into the trace — fine for one LUT sweep, hopeless for a 5k-step
    multiply program), trace time is O(1) in program length.  Rows past
    ``n_valid`` (block padding) are masked out of writes and counters, and
    sub-blocks holding none of the valid rows are skipped.
    """
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = arr_ref[...]
        if collect_stats:
            stats_refs[0][...] = jnp.zeros(stats_refs[0].shape, jnp.int32)

    n_local = n_valid_ref[0] - i * block_rows
    slot = _slot_reader(words_ref, lay, interpret)

    # Mosaic has no int8 vector compares on this path: digits are widened
    # to int32 for the replay and narrowed again on the store
    compute_dtype = jnp.int8 if interpret else jnp.int32

    def sub_block(r, carry):
        r0 = pl.multiple_of(r * sub_rows, sub_rows)

        @pl.when(r0 < n_local)
        def _run():
            blk = out_ref[pl.ds(r0, sub_rows), :].astype(compute_dtype)
            row_ok = (r0 + jax.lax.broadcasted_iota(
                jnp.int32, (sub_rows, 1), 0)) < n_local
            blk, counts = _program_block_body(
                blk, row_ok, slot, lay, chunk, collect_stats=collect_stats,
                hist_bins=hist_bins, unroll=unroll, variant=variant,
                pack=pack)
            out_ref[pl.ds(r0, sub_rows), :] = blk.astype(out_ref.dtype)
            if collect_stats:
                stats_refs[0][0:1, :2 + hist_bins] += counts

        return carry

    jax.lax.fori_loop(0, block_rows // sub_rows, sub_block, 0)


def _tap_lanes_kernel(n_valid_ref, words_ref, arr_ref, out_ref, *refs,
                      lay: SlotLayout, chunk: int, n_chunks: int,
                      interpret: bool, collect_stats: bool, hist_bins: int,
                      unroll: int):
    """Pallas body of the ``lanes`` variant: grid (row block ``i``,
    schedule chunk ``j``).

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
            collect_stats=collect_stats, hist_bins=hist_bins, unroll=unroll)
        if collect_stats:
            refs[0][0:1, :2 + hist_bins] += counts

    @pl.when(j == n_chunks - 1)
    def _store():
        def narrow(rows, planes):
            out_ref[rows, :] = blk_ref[:, planes, :].reshape(
                n_cols, tr_rows).T.astype(out_ref.dtype)
        each_tile(narrow)


def _lane_width(block_rows: int) -> int:
    """Rows per lane row of the ``lanes`` layout: the 128 lanes, or the
    whole block when it is not a multiple of them."""
    return 128 if block_rows % 128 == 0 else block_rows


def _tap_program_pallas(arr, sched, n_valid, *, block_rows: int,
                        collect_stats: bool, hist_bins: int, interpret: bool,
                        unroll: int, variant: str, pack: int):
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
        # a chunk holds whole VLIW groups, and a partial SMEM block must
        # span a multiple of the 1024-word SMEM tiling
        m = math.lcm(pack, 1024 // math.gcd(lay.width, 1024))
        chunk = m * -(-n_slots // (n_chunks * m))
    if n_chunks * chunk > n_slots:
        words = jnp.concatenate(
            [words, _noop_slots(n_chunks * chunk - n_slots, lay)])
    grid = (rows // block_rows, n_chunks)
    scratch = []
    if variant == "lanes":
        # rows onto sublanes x lanes: one [block_rows / lane, lane] plane
        # per column
        lane = _lane_width(block_rows)
        kernel = functools.partial(
            _tap_lanes_kernel, lay=lay, chunk=chunk, n_chunks=n_chunks,
            interpret=interpret, collect_stats=collect_stats,
            hist_bins=hist_bins, unroll=unroll)
        scratch = [pltpu.VMEM((cols, block_rows // lane, lane), jnp.int32)]
    else:
        sub_rows = (TPU_SUB_ROWS if not interpret
                    and block_rows > TPU_SUB_ROWS
                    and block_rows % TPU_SUB_ROWS == 0 else block_rows)
        kernel = functools.partial(
            _tap_program_kernel, lay=lay, chunk=chunk,
            block_rows=block_rows, sub_rows=sub_rows, interpret=interpret,
            collect_stats=collect_stats, hist_bins=hist_bins, unroll=unroll,
            variant=variant, pack=pack)
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
        out_shape=out_shape, scratch_shapes=scratch, interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(jnp.reshape(n_valid, (1,)).astype(jnp.int32), words.reshape(-1),
      jnp.asarray(arr, jnp.int8))
    if not collect_stats:
        return res[0], None
    counts = res[1].reshape(grid[0], 8, COUNT_LANES)[:, 0, :2 + hist_bins]
    return res[0], counts


def _tap_program_xla(padded, sched, n_valid, *, block_rows: int,
                     collect_stats: bool, hist_bins: int, unroll: int,
                     variant: str, pack: int):
    """Compiled-XLA path: the same step body mapped over row-blocks.

    Used when ``interpret=False`` on a non-TPU backend, where pallas has no
    compiled lowering — the body is ordinary static vector algebra, so
    plain jit gives the compiled semantics (and per-block counter layout)
    the TPU kernel has, bit-identically.
    """
    rows, cols = padded.shape
    grid = rows // block_rows
    words, lay = _schedule_words(*sched)

    def slot(s):
        row = jax.lax.dynamic_index_in_dim(words, s, keepdims=False)
        return lambda off: row[off]

    def per_block(args):
        i, blk = args
        if variant == "lanes":
            lane = _lane_width(block_rows)
            shape = (block_rows // lane, lane)
            row = (i * block_rows
                   + lane * jax.lax.broadcasted_iota(jnp.int32, shape, 0)
                   + jax.lax.broadcasted_iota(jnp.int32, shape, 1))
            planes, counts = _lanes_block_body(
                lambda st, c: jax.lax.dynamic_index_in_dim(
                    st, c, keepdims=False),
                lambda st, c, v: jax.lax.dynamic_update_index_in_dim(
                    st, v[None], c, 0),
                blk.astype(jnp.int32).T.reshape(cols, *shape),
                row < n_valid, slot, lay, words.shape[0],
                collect_stats=collect_stats, hist_bins=hist_bins,
                unroll=unroll)
            out = planes.reshape(cols, block_rows).T.astype(jnp.int8)
            return out, counts[0]
        row_ok = (i * block_rows + jax.lax.broadcasted_iota(
            jnp.int32, (block_rows, 1), 0)) < n_valid
        out, counts = _program_block_body(
            blk, row_ok, slot, lay, words.shape[0],
            collect_stats=collect_stats, hist_bins=hist_bins, unroll=unroll,
            variant=variant, pack=pack)
        return out, counts[0]

    # sequential lax.map over row-blocks, mirroring the pallas grid — vmap
    # batches the gather body's dynamic updates into scatter HLO that XLA
    # CPU lowers ~2x slower than the streamed per-block loop
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
                    interpret: bool | None = None, unroll: int | None = None,
                    variant: str = "gather", pack: int = 1):
    """Run a whole packed program: one launch, grid over row-blocks.

    Returns ``out`` (same shape as ``arr``) and, when ``collect_stats``, a
    per-grid-block (grid, 2 + hist_bins) int32 counter tensor laid out as
    [sets, resets, hist[0..hist_bins)] — summed over grid by the caller
    (still in-graph).  The schedule tensors are runtime args, so one
    compiled kernel serves every program with the same packed shape.

    ``variant`` selects the step-body formulation (see module docstring);
    ``pack`` > 1 marks group-major VLIW schedule tensors with
    ``n_slots % pack == 0`` (:meth:`repro.apc.lower.CompiledProgram.packed`
    produces them): the one-hot body replays a group per loop iteration,
    the lanes body its slots one by one.  ``interpret=None`` resolves per
    backend (see :func:`resolve_interpret`); ``interpret=False`` off-TPU
    runs the jitted XLA harness — same body, same per-block counter
    layout, bit-identical.
    The gather body has no Mosaic lowering: compiled on TPU it raises.
    """
    if variant not in VARIANTS:
        raise ValueError(f"variant must be one of {VARIANTS}, got {variant!r}")
    if pack < 1:
        raise ValueError(f"pack must be >= 1, got {pack}")
    if variant == "gather" and pack != 1:
        raise ValueError("the gather body applies writes serially; VLIW "
                         "packing requires variant='onehot'")
    # env-default resolution happens OUT here, before the jit boundary —
    # inside it the resolved value would be baked into the cache entry
    # keyed on the None static and never re-read on cache hits
    interpret = resolve_interpret(interpret)
    if variant == "gather" and not interpret \
            and jax.default_backend() == "tpu":
        raise ValueError("the gather body (dynamic column indexing) does "
                         "not compile under Mosaic; run variant='onehot'")
    return _tap_run_program_jit(
        arr, cmp_cols, keys, key_valid, hist_flag, wr_cols, wr_vals,
        n_valid_rows, block_rows=block_rows, collect_stats=collect_stats,
        hist_bins=hist_bins, interpret=interpret,
        unroll=resolve_unroll(unroll, variant, pack), variant=variant,
        pack=pack)


@functools.partial(jax.jit, static_argnames=(
    "block_rows", "collect_stats", "hist_bins", "interpret", "unroll",
    "variant", "pack"))
def _tap_run_program_jit(arr, cmp_cols, keys, key_valid, hist_flag,
                         wr_cols, wr_vals, n_valid_rows, *, block_rows: int,
                         collect_stats: bool, hist_bins: int,
                         interpret: bool, unroll: int, variant: str,
                         pack: int):
    rows = arr.shape[0]
    if rows % block_rows:
        raise ValueError(f"rows={rows} not a multiple of {block_rows}")
    sched = (cmp_cols, keys, key_valid, hist_flag, wr_cols, wr_vals)
    n_valid = jnp.asarray(n_valid_rows, jnp.int32)
    body_kw = dict(block_rows=block_rows, collect_stats=collect_stats,
                   hist_bins=hist_bins, unroll=unroll, variant=variant,
                   pack=pack)
    if not interpret and jax.default_backend() != "tpu":
        return _tap_program_xla(jnp.asarray(arr, jnp.int8), sched, n_valid,
                                **body_kw)
    return _tap_program_pallas(arr, sched, n_valid, interpret=interpret,
                               **body_kw)


@functools.partial(jax.jit, static_argnames=("schedule", "block_rows"))
def tap_apply_schedule(arr: jax.Array, schedule: tuple[Step, ...],
                       block_rows: int = BLOCK_ROWS) -> jax.Array:
    """Apply a fused LUT schedule to the digit array via pallas_call, on
    the pallas interpreter (the unrolled body has no Mosaic lowering; the
    compiled route is :func:`tap_run_program`).

    ``arr``: [rows, cols] int8, rows % block_rows == 0 (pad with don't-care
    rows if needed — they never match and are returned unchanged).
    """
    rows, cols = arr.shape
    if rows % block_rows:
        raise ValueError(f"rows={rows} not a multiple of {block_rows}")
    grid = (rows // block_rows,)
    return pl.pallas_call(
        functools.partial(_tap_kernel, schedule=schedule),
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, cols), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, cols), jnp.int8),
        interpret=True,
    )(arr)
