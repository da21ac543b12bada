"""Plain reference of the TAP in-place ripple adder (arXiv:2110.09643 §IV).

Written from the paper, independent of the program under test:

- ``full_adder_passes`` builds the non-blocked LUT of the radix-r full adder
  ``(A, B, C) -> (A, (A+B+C) mod r, (A+B+C) div r)`` by Algorithm 1: the
  state diagram (one out-edge per state), its cycles broken by redirecting
  an edge to an output that differs only in the unwritten A digit (the
  paper's TFA example redirects 101 -> 120 to 101 -> 020, a 3-digit
  write), and a depth-first preorder from the no-action roots so that a
  state's pass runs before any pass that writes it.
- ``replay`` applies the multi-digit add to digit rows pass by pass: one
  unconditional write clears the carry column, then for each digit position
  every pass compares its key with (A_i, B_i, C) and writes the matching
  rows.  It returns the digits and, per row, the paper's counters: SETs and
  RESETs (a changed digit costs one of each), and the mismatch histogram of
  every compare (index k: k of the 3 compared digits differ).
"""
from __future__ import annotations

import itertools

import numpy as np

HIST_BINS = 4            # 0..3 mismatching digits of a 3-digit key


def full_adder(radix: int):
    def f(x):
        a, b, c = x
        s = a + b + c
        return (a, s % radix, s // radix)
    return f


def full_adder_passes(radix: int) -> list[tuple[tuple, tuple, tuple]]:
    """The non-blocked LUT: ``[(key, write_cols, write_vals)]`` in order,
    over the logical columns (A, B, C)."""
    f = full_adder(radix)
    states = list(itertools.product(range(radix), repeat=3))
    out = {x: f(x) for x in states}
    writes = {x: (1, 2) for x in states}
    no_action = {x for x in states if out[x] == x}

    def reaches(src, dst):
        seen, cur = set(), src
        while cur not in seen:
            if cur == dst:
                return True
            seen.add(cur)
            if out[cur] == cur:
                return False
            cur = out[cur]
        return False

    def find_cycle():
        done = set()
        for start in states:
            path, cur = [], start
            while cur not in done and cur not in path and out[cur] != cur:
                path.append(cur)
                cur = out[cur]
            if cur in path and len(path) - path.index(cur) >= 2:
                return path[path.index(cur):]
            done.update(path)
        return None

    while (cycle := find_cycle()) is not None:
        for x in sorted(cycle):
            y = f(x)
            alts = [(a,) + y[1:] for a in range(radix) if a != y[0]]
            alts.sort(key=lambda z: (z not in no_action, z))
            alts = [z for z in alts if not reaches(z, x)]
            if alts:
                z = alts[0]
                out[x] = z
                writes[x] = (0, 1, 2) if z[0] != x[0] else (1, 2)
                break
        else:
            raise ValueError(f"cannot break the cycle {cycle}")

    children = {x: [] for x in states}
    for x in states:
        if x not in no_action:
            children[out[x]].append(x)
    passes = []

    def visit(x):
        if x not in no_action:
            passes.append((x, writes[x], tuple(out[x][c] for c in writes[x])))
        for child in sorted(children[x]):
            visit(child)

    for root in sorted(no_action):
        visit(root)
    return passes


def encode(a: np.ndarray, b: np.ndarray, radix: int, width: int
           ) -> np.ndarray:
    """Little-endian digit rows [A_0..A_{w-1} | B_0..B_{w-1} | C=0]."""
    arr = np.zeros((len(a), 2 * width + 1), np.int8)
    for i in range(width):
        arr[:, i] = (a // radix ** i) % radix
        arr[:, width + i] = (b // radix ** i) % radix
    return arr


def cycles(radix: int, width: int) -> tuple[int, int]:
    """(compare cycles, write cycles) of the whole add: one compare and one
    write per pass per digit, plus the write that clears the carry."""
    n = len(full_adder_passes(radix)) * width
    return n, n + 1


def replay(arr, radix: int, width: int):
    """The add on digit rows ``arr`` [R, 2w+1] (a jax array; runs where it
    lives).  Returns ``(digits, sets[R], resets[R], hist[R, HIST_BINS])``."""
    import jax
    import jax.numpy as jnp
    passes = full_adder_passes(radix)
    carry = 2 * width

    def digit(i, state):
        arr, sets, resets, hist = state
        cols = (i, width + i, carry)
        for key, wcols, wvals in passes:
            cells = [arr[:, c] if isinstance(c, int) else
                     jax.lax.dynamic_index_in_dim(arr, c, 1, keepdims=False)
                     for c in cols]
            miss = sum((cell != k).astype(jnp.int32)
                       for cell, k in zip(cells, key))
            hist = hist + jax.nn.one_hot(miss, HIST_BINS, dtype=jnp.int32)
            tag = miss == 0
            for wc, v in zip(wcols, wvals):
                old = cells[wc]
                changed = tag & (old != v)
                sets = sets + changed.astype(jnp.int32)
                resets = resets + changed.astype(jnp.int32)
                new = jnp.where(changed, jnp.int8(v), old)
                arr = jax.lax.dynamic_update_index_in_dim(
                    arr, new, cols[wc], 1)
                cells[wc] = new
        return arr, sets, resets, hist

    def run(arr):
        rows = arr.shape[0]
        old = arr[:, carry]
        changed = old != 0
        sets = changed.astype(jnp.int32)
        resets = changed.astype(jnp.int32)
        arr = arr.at[:, carry].set(0)
        hist = jnp.zeros((rows, HIST_BINS), jnp.int32)
        state = (arr, sets, resets, hist)
        step = jax.jit(digit)
        for i in range(width):
            state = step(jnp.int32(i), state)
        return state

    return run(jnp.asarray(arr, jnp.int8))
