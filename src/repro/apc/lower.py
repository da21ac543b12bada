"""Lowering: microcode IR -> one static flat Step schedule -> dense tensors.

``lower`` unrolls :class:`~repro.apc.ir.ForDigit` loops, resolves affine
column expressions, and flattens every op into :class:`Step`s — the same
(keys, compare_cols) -> (write_cols, write_vals) shape the tap_pass kernel
replays, plus an ``in_hist`` flag so the traced stats reproduce the
functional simulator's counters exactly (repair compares are charged as
cycles but not histogrammed).

:class:`CompiledProgram` packs a Step schedule into dense int tensors (keys
/ columns padded to the schedule-wide maxima), which the program kernel
fori-loops over instead of unrolling hundreds of passes into the trace;
executors hand :attr:`CompiledProgram.schedule_tensors` to
:func:`~repro.kernels.tap_pass.kernel.tap_run_program` as they are.

``compile_program`` caches (lower + pack) per program identity;
``compile_named`` caches whole (fn, radix, width) programs — e.g. the 20-trit
adder schedule is built exactly once per process.  Every compilation cache
here (and in :mod:`repro.apc.mac` / the LUT builders) is size-bounded;
:mod:`repro.apc.caches` registers them all and serves occupancy stats.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import numpy as np

from ..core import truth_tables as tt
from ..core.blocked import build_lut_blocked
from ..core.lut import LUT
from ..core.nonblocked import build_lut_nonblocked
from . import trace
from .ir import (ApplyLUT, Col, CompareWrite, ForDigit, Op, Program, SetCol,
                 ZeroCol, digit, resolve_col)


@dataclass(frozen=True)
class Step:
    """One flattened compare-block + write: OR of ``keys`` over
    ``compare_cols`` tags the rows, then one write cycle lands.  No keys =
    unconditional write.  ``in_hist`` gates the mismatch histogram."""
    keys: tuple[tuple[int, ...], ...]
    compare_cols: tuple[int, ...]
    write_cols: tuple[int, ...]
    write_vals: tuple[int, ...]
    in_hist: bool = True

    @property
    def n_compares(self) -> int:
        return len(self.keys)


def lower(program: Program, env: dict[str, int] | None = None
          ) -> tuple[Step, ...]:
    """Flatten a program into a static Step schedule."""
    env = env or {}
    steps: list[Step] = []
    for op in program:
        steps.extend(_lower_op(op, env))
    return tuple(steps)


def _lower_op(op: Op, env: dict[str, int]) -> list[Step]:
    if isinstance(op, SetCol):
        return [Step(keys=(), compare_cols=(),
                     write_cols=(resolve_col(op.col, env),),
                     write_vals=(int(op.val),), in_hist=False)]
    if isinstance(op, ApplyLUT):
        cols = tuple(resolve_col(c, env) for c in op.col_map)
        xcols = tuple(resolve_col(c, env) for c, _ in op.extra_key)
        xvals = tuple(int(v) for _, v in op.extra_key)
        out = []
        for blk in op.lut.blocks:
            out.append(Step(
                keys=tuple(tuple(k) + xvals for k in blk.keys),
                compare_cols=cols + xcols,
                write_cols=tuple(cols[c] for c in blk.write_cols),
                write_vals=tuple(blk.write_vals)))
        return out
    if isinstance(op, CompareWrite):
        return [Step(keys=(tuple(op.key),),
                     compare_cols=tuple(resolve_col(c, env)
                                        for c in op.compare_cols),
                     write_cols=tuple(resolve_col(c, env)
                                      for c in op.write_cols),
                     write_vals=tuple(op.write_vals),
                     in_hist=op.count_mismatch)]
    if isinstance(op, ForDigit):
        out = []
        for v in range(op.start, op.stop):
            sub = dict(env)
            sub[op.var] = v
            for body_op in op.body:
                out.extend(_lower_op(body_op, sub))
        return out
    raise TypeError(f"unknown IR op {op!r}")


# ---------------------------------------------------------------------------
# Packing: Step schedule -> dense schedule tensors for the fori_loop kernel
# ---------------------------------------------------------------------------

class CompiledProgram:
    """A lowered + packed program, ready for the fused executor.

    Dense layout (S steps, K = max keys/step, C = max compare cols,
    W = max write cols; -1 pads invalid columns, key_valid masks pad keys):

    - ``cmp_cols``  (S, C) int32   - ``keys``     (S, K, C) int8
    - ``key_valid`` (S, K) bool    - ``hist_flag`` (S,) bool
    - ``wr_cols``   (S, W) int32   - ``wr_vals``  (S, W) int8

    Cycle counts are schedule-static: one write cycle per step, one compare
    cycle per valid key — identical to the pass-by-pass simulator's charges.
    """

    def __init__(self, steps: tuple[Step, ...], min_cols: int = 0):
        if not steps:
            raise ValueError("empty program")
        self.steps = steps
        S = len(steps)
        K = max(1, max(s.n_compares for s in steps))
        C = max(1, max(len(s.compare_cols) for s in steps))
        W = max(1, max(len(s.write_cols) for s in steps))
        self.cmp_cols = np.full((S, C), -1, np.int32)
        self.keys = np.zeros((S, K, C), np.int8)
        self.key_valid = np.zeros((S, K), bool)
        self.hist_flag = np.zeros((S,), bool)
        self.wr_cols = np.full((S, W), -1, np.int32)
        self.wr_vals = np.zeros((S, W), np.int8)
        cols_seen = 0
        for s, st in enumerate(steps):
            nc = len(st.compare_cols)
            self.cmp_cols[s, :nc] = st.compare_cols
            for k, key in enumerate(st.keys):
                self.keys[s, k, :nc] = key
                self.key_valid[s, k] = True
            self.hist_flag[s] = st.in_hist and bool(st.keys)
            nw = len(st.write_cols)
            self.wr_cols[s, :nw] = st.write_cols
            self.wr_vals[s, :nw] = st.write_vals
            cols_seen = max(cols_seen, *(c + 1 for c in st.compare_cols),
                            *(c + 1 for c in st.write_cols), 1)
        self.min_cols = max(min_cols, cols_seen)
        self.n_compare_cycles = int(self.key_valid.sum())
        self.n_write_cycles = S

    @property
    def n_steps(self) -> int:
        return len(self.steps)

    @property
    def schedule_tensors(self) -> tuple[np.ndarray, ...]:
        """The 6 dense tensors the program kernel replays, flat order."""
        return (self.cmp_cols, self.keys, self.key_valid, self.hist_flag,
                self.wr_cols, self.wr_vals)

    def as_tap_steps(self):
        """The 4-tuple step form of the :mod:`kernels.tap_pass.ref` oracle."""
        return tuple((s.keys, s.compare_cols, s.write_cols, s.write_vals)
                     for s in self.steps)


@functools.lru_cache(maxsize=256)
def _compile_steps(steps: tuple[Step, ...]) -> CompiledProgram:
    return CompiledProgram(steps)


def compile_program(program: Program) -> CompiledProgram:
    """Lower + pack, cached on the flattened schedule (Step tuples hash)."""
    steps = lower(program)
    return trace.traced_compile("compile_steps", _compile_steps, steps,
                                _label=f"steps[{len(steps)}]")


# ---------------------------------------------------------------------------
# Program builders (mirror the core/ap.py drivers pass-for-pass)
# ---------------------------------------------------------------------------

def ripple_add_program(lut: LUT, width: int, carry_col: int, a_base: int = 0,
                       b_base: int | None = None, zero_carry: bool = True
                       ) -> Program:
    """B <- A + B, digit-serial carry ripple (paper §IV multi-trit add)."""
    b_base = width if b_base is None else b_base
    i = digit("i")
    prog: list[Op] = [ZeroCol(carry_col)] if zero_carry else []
    prog.append(ForDigit("i", 0, width,
                         (ApplyLUT(lut, (a_base + i, b_base + i, carry_col)),)))
    return tuple(prog)


def ripple_sub_program(lut_sub: LUT, width: int, borrow_col: int,
                       a_base: int = 0, b_base: int | None = None,
                       zero_carry: bool = True) -> Program:
    """B <- A - B (mod r^p), borrow ripples."""
    b_base = width if b_base is None else b_base
    i = digit("i")
    prog: list[Op] = [ZeroCol(borrow_col)] if zero_carry else []
    prog.append(ForDigit("i", 0, width,
                         (ApplyLUT(lut_sub,
                                   (a_base + i, b_base + i, borrow_col)),)))
    return tuple(prog)


def multiply_program(lut_add: LUT, lut_half: LUT, width: int, radix: int,
                     a_base: int, acopy_base: int, b_base: int, r_base: int,
                     carry_col: int) -> Program:
    """R <- A * B by shift-and-add with A-repair sweeps.

    Identical op-for-op to :func:`repro.core.ap.multiply`: for each
    multiplier digit B_j and weight t, t predicated add-sweeps of A into
    R<<j, a half-adder carry ripple through the upper product digits, then
    (when the adder's cycle-breaking dummy-writes the A column) a repair
    sweep restoring A from the pristine copy A'.  The digit loops are
    ForDigit IR; the (j, t, repetition) structure — whose trip counts depend
    on t — is unrolled here at build time.
    """
    adder_writes_a = any(0 in p.write_cols for p in lut_add.passes)
    i = digit("i")
    prog: list[Op] = []
    for j in range(width):
        for t in range(1, radix):
            for _ in range(t):
                prog.append(ZeroCol(carry_col))
                prog.append(ForDigit("i", 0, width, (
                    ApplyLUT(lut_add,
                             (a_base + i, r_base + j + i, carry_col),
                             extra_key=((b_base + j, t),)),)))
                prog.append(ForDigit("k", j + width, 2 * width, (
                    ApplyLUT(lut_half, (r_base + digit("k"), carry_col)),)))
                if adder_writes_a:
                    repair = tuple(
                        CompareWrite(compare_cols=(acopy_base + i,),
                                     key=(v,),
                                     write_cols=(a_base + i,),
                                     write_vals=(v,))
                        for v in range(1, radix))
                    prog.append(ForDigit("i", 0, width, repair))
    return tuple(prog)


def negate_program(lut_not_copy: LUT, lut_half: LUT, width: int,
                   b_base: int, r_base: int, carry_col: int) -> Program:
    """R <- (-B) mod r^p (radix complement): digitwise diminished-radix
    complement of B into R via the 2-column inverter LUT, then +1 by seeding
    the carry column and rippling the half adder through R."""
    i = digit("i")
    return (
        ForDigit("i", 0, width,
                 (ApplyLUT(lut_not_copy, (b_base + i, r_base + i)),)),
        SetCol(carry_col, 1),
        ForDigit("i", 0, width,
                 (ApplyLUT(lut_half, (r_base + i, carry_col)),)),
    )


def elementwise_program(lut2: LUT, width: int, a_base: int = 0,
                        b_base: int | None = None) -> Program:
    """Digitwise 2-input MVL op B_i <- f(A_i, B_i) (min/max/modsum/...)."""
    b_base = width if b_base is None else b_base
    i = digit("i")
    return (ForDigit("i", 0, width,
                     (ApplyLUT(lut2, (a_base + i, b_base + i)),)),)


def checksum_program(n_cols: int, radix: int, cs_col: int | None = None
                     ) -> Program:
    """Mod-r row checksum fold: ``cs <- sum(col_0..col_{n-1}) mod r``.

    The fault-detection program (:mod:`repro.apc.faults`): zero the
    checksum column, then fold every data column in with the ``modsum``
    2-input LUT (``(a, b) -> (a, (a+b) mod r)``).  Compiling it through
    the normal IR means every compare/write cycle of detection is priced
    by the same schedule-static accounting as real programs — checksum
    verification shows up honestly in ``APStats``.
    """
    if n_cols < 1:
        raise ValueError(f"need at least one data column, got {n_cols}")
    cs = n_cols if cs_col is None else cs_col
    lut = build_lut_nonblocked(tt.REGISTRY["modsum"](radix))
    return (ZeroCol(cs),) + tuple(ApplyLUT(lut, (c, cs))
                                  for c in range(n_cols))


@functools.lru_cache(maxsize=64)
def _compile_checksum_cached(n_cols: int, radix: int) -> CompiledProgram:
    return compile_program(checksum_program(n_cols, radix))


def compile_checksum(n_cols: int, radix: int) -> CompiledProgram:
    """Compiled mod-r checksum fold over ``n_cols`` data columns, writing
    column ``n_cols`` (cached; registered in :mod:`repro.apc.caches`)."""
    return trace.traced_compile(
        "compile_checksum", _compile_checksum_cached, n_cols, radix,
        _label=f"checksum:{n_cols}c:r{radix}")


# ---------------------------------------------------------------------------
# Whole-program cache keyed on (fn, radix, width)
# ---------------------------------------------------------------------------

def compile_named(fn: str, radix: int, width: int, *, blocked: bool = False
                  ) -> CompiledProgram:
    """Compile a standard multi-digit program by name, cached (with
    compile-span + cache hit/miss telemetry; see :mod:`repro.apc.trace`).

    See :func:`_compile_named_cached` for the program layouts.
    """
    return trace.traced_compile(
        "compile_named", _compile_named_cached, fn, radix, width,
        blocked=blocked, _label=f"{fn}:r{radix}:w{width}")


@functools.lru_cache(maxsize=128)
def _compile_named_cached(fn: str, radix: int, width: int, *,
                          blocked: bool = False) -> CompiledProgram:
    """Compile a standard multi-digit program by name, cached.

    Layouts (little-endian digit columns, matching core/ap.py drivers):

    - ``add``/``sub``:          [A(w) | B(w) | C]        -> 2w+1 columns
    - ``mul``:                  [A | A' | B | R(2w) | C] -> 5w+1 columns
    - ``negate``:               [B(w) | R(w) | C]        -> 2w+1 columns
    - ``min``/``max``/``modsum``/``nor``/``nand``: [A | B] -> 2w columns
    """
    build = build_lut_blocked if blocked else build_lut_nonblocked
    if fn == "add":
        lut = build(tt.full_adder(radix))
        prog = ripple_add_program(lut, width, carry_col=2 * width)
    elif fn == "sub":
        lut = build(tt.full_subtractor(radix))
        prog = ripple_sub_program(lut, width, borrow_col=2 * width)
    elif fn == "mul":
        lut_add = build(tt.full_adder(radix))
        lut_half = build(tt.half_adder(radix))
        prog = multiply_program(lut_add, lut_half, width, radix,
                                a_base=0, acopy_base=width, b_base=2 * width,
                                r_base=3 * width, carry_col=5 * width)
    elif fn == "negate":
        lut_not = build(tt.tnot_copy(radix))
        lut_half = build(tt.half_adder(radix))
        prog = negate_program(lut_not, lut_half, width, b_base=0,
                              r_base=width, carry_col=2 * width)
    elif fn in ("min", "max", "modsum", "nor", "nand"):
        lut = build(tt.REGISTRY[fn](radix))
        prog = elementwise_program(lut, width)
    else:
        raise ValueError(f"unknown program {fn!r}")
    return compile_program(prog)
