"""Array-pool pipelined executor + K-tiled MAC programs.

Acceptance contract (ISSUE 3): ArrayPool output and APStats are
bit-identical to single-array execute across (n_arrays, pool rows, k_tile)
grids at radix 3/4/5; tiled MAC programs (partial sums + ripple-add
reduction) equal the untiled program digit-for-digit with cycle counts
that are the exact sum of the constituent programs; and
``ternary_matmul(impl="ap")`` with a column budget forcing >= 2 K-tiles
over >= 2 arrays is bit-exact vs ``impl="ref"``.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro import apc
from repro.core import ap, build_lut_nonblocked, truth_tables as tt
from repro.kernels.ternary_matmul.ap import (ap_matmul_cycle_counts,
                                             default_k_tile,
                                             ternary_matmul_ap)
from repro.kernels.ternary_matmul.ops import (quantize_and_pack,
                                              ternary_matmul)
from repro.kernels.ternary_matmul.ref import ternary_matmul_ref


def _stats_equal(a: ap.APStats, b: ap.APStats) -> None:
    assert (a.sets, a.resets) == (b.sets, b.resets)
    assert (a.n_compare_cycles, a.n_write_cycles) == \
        (b.n_compare_cycles, b.n_write_cycles)
    assert np.array_equal(a.mismatch_hist, b.mismatch_hist)


def _pool_stats(pool, traced, compiled, rows, radix):
    st = ap.APStats(radix=radix)
    apc.accumulate(st, traced, compiled, n_rows=rows)
    return st


# ---------------------------------------------------------------------------
# ArrayPool vs single-array execute: bit parity across the grid
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("radix", [3, 4, 5])
@pytest.mark.parametrize("n_arrays,pool_rows", [(1, 64), (2, 32), (3, 16)])
def test_pool_parity_vs_execute(radix, n_arrays, pool_rows):
    """Named add program: same digits, same APStats, any pool geometry."""
    w, rows = 4, 101                      # blocks of 64/32/16 rows + a tail
    rng = np.random.default_rng(radix * 13 + n_arrays)
    a = rng.integers(0, radix ** w, rows)
    b = rng.integers(0, radix ** w, rows)
    arr = jnp.asarray(ap.encode_operands(a, b, radix, w))
    compiled = apc.compile_named("add", radix, w)
    out_e, tr_e = apc.execute(arr, compiled, collect_stats=True)
    pool = apc.ArrayPool(n_arrays=n_arrays, rows=pool_rows, cols=2 * w + 1)
    out_p, tr_p = pool.run(arr, compiled, collect_stats=True)
    assert np.array_equal(np.asarray(out_e), np.asarray(out_p))
    _stats_equal(_pool_stats(pool, tr_e, compiled, rows, radix),
                 _pool_stats(pool, tr_p, compiled, rows, radix))
    # pipelined wall-cycle model: waves = ceil(n_blocks / n_arrays)
    wall = pool.wall_cycles(rows, compiled.n_compare_cycles,
                            compiled.n_write_cycles)
    n_blocks = -(-rows // pool_rows)
    waves = -(-n_blocks // n_arrays)
    assert wall["waves"] == waves
    assert wall["write_cycles"] == waves * compiled.n_write_cycles


@pytest.mark.parametrize("radix", [3, 4, 5])
@pytest.mark.parametrize("k_tile", [1, 2, 3])
def test_pool_tiled_mac_parity_vs_untiled(radix, k_tile):
    """Tiled partial sums + reduction equal the untiled MAC bit-for-bit,
    and tiled cycle counts are the exact sum of tiles + reduction."""
    K, max_abs, rows = 5, 3, 43
    width = apc.mac_acc_width(radix, K, max_abs)
    rng = np.random.default_rng(radix * 19 + k_tile)
    x = rng.integers(-max_abs, max_abs + 1, (rows, K))
    w = rng.integers(-1, 2, (rows, K))
    want = (x * w).sum(axis=1)
    # untiled oracle digits
    arr = jnp.asarray(apc.encode_mac_rows(x, w, radix, width))
    compiled = apc.compile_mac(radix, K, width)
    out_u, _ = apc.execute(arr, compiled)
    assert np.array_equal(apc.decode_mac_acc(np.asarray(out_u), radix, K,
                                             width), want)
    # tiled over a pool whose columns fit exactly the largest tile row
    cols = max(apc.mac_layout(min(k_tile, K), width)["n_cols"],
               2 * width + 1)
    pool = apc.ArrayPool(n_arrays=2, rows=16, cols=cols)
    tiled = apc.compile_mac_tiled(radix, K, width, k_tile,
                                  max_cols=pool.cols)
    st = ap.APStats(radix=radix)
    acc = apc.run_mac_tiled(jnp.asarray(x, jnp.int32),
                            jnp.asarray(w, jnp.int8), tiled, pool=pool,
                            stats=st)
    assert np.array_equal(np.asarray(acc), want)
    progs = tiled.programs + tiled.reduce_programs
    assert st.n_write_cycles == sum(p.n_write_cycles for p in progs)
    assert st.n_compare_cycles == sum(p.n_compare_cycles for p in progs)
    assert tiled.n_write_cycles == st.n_write_cycles
    if k_tile < K:
        assert len(tiled.tiles) >= 2 and tiled.reduce_programs


def test_pool_tiled_mac_stats_match_untiled_rowwork():
    """Sets/resets/histogram are per-row work: the tile programs must do
    exactly what the untiled sweeps do (the reduction adds its own)."""
    radix, K, k_tile, max_abs, rows = 3, 4, 2, 2, 29
    width = apc.mac_acc_width(radix, K, max_abs)
    rng = np.random.default_rng(7)
    x = rng.integers(-max_abs, max_abs + 1, (rows, K))
    w = rng.integers(-1, 2, (rows, K))
    su, stt = ap.APStats(radix=radix), ap.APStats(radix=radix)
    arr = jnp.asarray(apc.encode_mac_rows(x, w, radix, width))
    out_u = apc.run(arr, apc.compile_mac(radix, K, width), stats=su)
    tiled = apc.compile_mac_tiled(radix, K, width, k_tile)
    acc = apc.run_mac_tiled(jnp.asarray(x, jnp.int32),
                            jnp.asarray(w, jnp.int8), tiled, stats=stt)
    want = (x * w).sum(axis=1)
    assert np.array_equal(np.asarray(acc), want)
    assert np.array_equal(
        np.asarray(apc.decode_mac_acc_jnp(out_u, radix, K, width)), want)
    # tiled row work >= untiled (reduction sweeps add mass, never drop it)
    assert stt.sets >= su.sets
    assert stt.mismatch_hist.sum() >= su.mismatch_hist.sum()


def test_pool_column_budget_enforced():
    width = 3
    compiled = apc.compile_mac(3, 8, width)      # needs 8*4+4 = 36 cols
    pool = apc.ArrayPool(n_arrays=2, rows=8, cols=16)
    arr = jnp.zeros((4, 36), jnp.int8)
    with pytest.raises(ValueError, match="tiled"):
        pool.run(arr, compiled)
    # rows wider than the physical array are rejected even if the program fits
    small = apc.compile_named("add", 3, 2)       # 5 cols
    with pytest.raises(ValueError, match="digit columns"):
        pool.run(jnp.zeros((4, 30), jnp.int8), small)
    with pytest.raises(ValueError, match="n_arrays"):
        apc.ArrayPool(n_arrays=0)


def test_pool_validate_up_front_names_width():
    """run/run_pooled/run_mac_tiled reject an over-wide program BEFORE any
    schedule upload or launch, naming the program width (regression: an
    oversized schedule used to reach the kernel, indexing out of bounds or
    silently clamping depending on jit mode)."""
    compiled = apc.compile_mac(3, 8, 3)          # 36-column MAC row
    pool = apc.ArrayPool(n_arrays=1, rows=8, cols=16)
    with pytest.raises(ValueError, match="36 columns wide"):
        apc.run_pooled(jnp.zeros((4, 36), jnp.int8), compiled, pool)
    with pytest.raises(ValueError, match="36 columns wide"):
        pool.run(jnp.zeros((4, 36), jnp.int8), compiled)
    assert len(pool._schedules) == 0             # nothing was uploaded
    # run_mac_tiled validates every constituent program up front too
    tiled = apc.compile_mac_tiled(3, 8, 3, 4)    # 20-column tile rows
    with pytest.raises(ValueError, match="columns wide"):
        apc.run_mac_tiled(jnp.zeros((4, 8), jnp.int32),
                          jnp.zeros((4, 8), jnp.int8), tiled, pool=pool)
    # fits exactly: no error
    pool_ok = apc.ArrayPool(n_arrays=1, rows=8, cols=36)
    pool_ok.validate(compiled, n_cols=36)


def test_pool_schedule_store_uploads_once_per_program():
    """The pool uploads a program's schedule tensors on its first run and
    reuses them on every later run (``pool.schedule_uploads`` /
    ``pool.schedule_reuse``); a second program gets an entry of its own,
    and the store stays bounded, re-uploading an evicted program."""
    from repro.apc.metrics import get_registry
    reg = get_registry()
    ups, reuse = (reg.counter("pool.schedule_uploads"),
                  reg.counter("pool.schedule_reuse"))
    add, mx = apc.compile_named("add", 3, 2), apc.compile_named("max", 3, 2)
    pool = apc.ArrayPool(n_arrays=2, rows=8, cols=16)
    arr = jnp.zeros((20, 5), jnp.int8)
    u0, r0 = ups.value, reuse.value
    first, _ = pool.run(arr, add)
    again, _ = pool.run(arr, add)
    assert (ups.value - u0, reuse.value - r0) == (1, 1)
    assert np.array_equal(np.asarray(first), np.asarray(again))
    pool.run(arr[:, :4], mx)
    assert (ups.value - u0, reuse.value - r0) == (2, 1)
    assert set(pool._schedules) == {id(add), id(mx)}
    pool._max_schedules = 2
    pool.run(arr, apc.compile_named("sub", 3, 2))   # evicts add's entry
    assert id(add) not in pool._schedules
    pool.run(arr, add)
    assert (ups.value - u0, reuse.value - r0) == (4, 1)


def test_pool_reduce_plan_chains_under_budget():
    """Many tiles + tight budget: the reduction chains in groups, still
    bit-exact."""
    radix, K, k_tile, max_abs, rows = 3, 9, 1, 1, 17
    width = apc.mac_acc_width(radix, K, max_abs)    # 9 partials to fold
    max_cols = 3 * width + 1                        # only 3 partials per row
    tiled = apc.compile_mac_tiled(radix, K, width, k_tile,
                                  max_cols=max_cols)
    assert len(tiled.reduce_groups) > 1
    assert all(g * width + 1 <= max_cols for g in tiled.reduce_groups)
    rng = np.random.default_rng(23)
    x = rng.integers(-max_abs, max_abs + 1, (rows, K))
    w = rng.integers(-1, 2, (rows, K))
    acc = apc.run_mac_tiled(jnp.asarray(x, jnp.int32),
                            jnp.asarray(w, jnp.int8), tiled)
    assert np.array_equal(np.asarray(acc), (x * w).sum(axis=1))
    with pytest.raises(ValueError, match="budget"):
        # even a 1-term MAC row needs 2*width + 2 columns
        apc.compile_mac_tiled(radix, K, width, 1, max_cols=2 * width)


def test_pool_run_mac_tiled_k_mismatch():
    tiled = apc.compile_mac_tiled(3, 4, 3, 2)
    with pytest.raises(ValueError, match="K="):
        apc.run_mac_tiled(jnp.zeros((2, 5), jnp.int32),
                          jnp.zeros((2, 5), jnp.int8), tiled)


# ---------------------------------------------------------------------------
# ternary_matmul(impl="ap") through the pool (the acceptance scenario)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("radix", [3, 4, 5])
def test_ternary_matmul_ap_pool_two_tiles_two_arrays(radix):
    """Column budget forcing >= 2 K-tiles over >= 2 arrays: bit-exact vs
    impl="ref" with exact write-cycle accounting (sum of tile programs +
    reduction)."""
    rng = np.random.default_rng(radix * 31)
    m, k, n, max_abs = 3, 24, 4, 3
    w = jnp.asarray(rng.normal(0, 0.05, (k, n)), jnp.float32)
    packed, scale = quantize_and_pack(w)
    kp = packed.shape[0] * 16
    x = jnp.asarray(rng.integers(-max_abs, max_abs + 1, (m, k)), jnp.float32)
    width = apc.mac_acc_width(radix, kp, max_abs)
    cols = apc.mac_layout(12, width)["n_cols"]     # 12-term tiles: >= 2 tiles
    pool = apc.ArrayPool(n_arrays=2, rows=8, cols=cols)
    st = ap.APStats(radix=radix)
    y = ternary_matmul(x, packed, scale, impl="ap", radix=radix, pool=pool,
                       stats=st)
    y_ref = ternary_matmul_ref(x, packed, scale)
    assert np.array_equal(np.asarray(y), np.asarray(y_ref))
    kt = default_k_tile(cols, width)
    cyc = ap_matmul_cycle_counts(radix, kp, width, k_tile=kt)
    assert cyc["n_tiles"] >= 2
    assert st.n_write_cycles == cyc["write_cycles"]
    assert st.n_compare_cycles == cyc["compare_cycles"]
    # pool.run streamed m*n = 12 rows through 8-row arrays: 2 blocks
    assert pool.n_blocks(m * n) == 2


def test_ternary_matmul_ap_k_tile_without_pool_matches_ref():
    rng = np.random.default_rng(5)
    m, k, n = 4, 16, 3
    w = jnp.asarray(rng.normal(0, 0.05, (k, n)), jnp.float32)
    packed, scale = quantize_and_pack(w)
    x = jnp.asarray(rng.integers(-2, 3, (m, k)), jnp.float32)
    y = ternary_matmul_ap(x, packed, scale, k_tile=6)
    assert np.array_equal(np.asarray(y),
                          np.asarray(ternary_matmul_ref(x, packed, scale)))


def test_ternary_matmul_ap_pool_rejects_oversized_k_tile():
    rng = np.random.default_rng(6)
    w = jnp.asarray(rng.normal(0, 0.05, (16, 2)), jnp.float32)
    packed, scale = quantize_and_pack(w)
    x = jnp.asarray(rng.integers(-2, 3, (2, 16)), jnp.float32)
    width = apc.mac_acc_width(3, 16, 2)
    pool = apc.ArrayPool(n_arrays=2, rows=8,
                         cols=apc.mac_layout(4, width)["n_cols"])
    with pytest.raises(ValueError, match="k_tile"):
        ternary_matmul_ap(x, packed, scale, pool=pool, k_tile=16)


# ---------------------------------------------------------------------------
# Device-side encode/decode
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("radix", [3, 4, 5])
def test_encode_decode_jnp_matches_numpy(radix):
    K, max_abs = 6, 5
    width = apc.mac_acc_width(radix, K, max_abs)
    rng = np.random.default_rng(radix)
    x = rng.integers(-max_abs, max_abs + 1, (33, K))
    w = rng.integers(-1, 2, (33, K))
    host = apc.encode_mac_rows(x, w, radix, width)
    dev = apc.encode_mac_rows_jnp(jnp.asarray(x, jnp.int32),
                                  jnp.asarray(w, jnp.int8), radix, width)
    assert np.array_equal(host, np.asarray(dev))
    # decode round-trip on raw signed values, incl. the negative extreme
    vals = np.concatenate([rng.integers(-(radix ** width - 1) // 2,
                                        (radix ** width - 1) // 2 + 1, 64),
                           [-(radix ** width - 1) // 2, 0,
                            (radix ** width - 1) // 2]])
    digs = np.zeros((len(vals), width), np.int8)
    for i in range(width):
        digs[:, i] = (vals // radix ** i) % radix
    got = np.asarray(apc.decode_signed_digits_jnp(jnp.asarray(digs), radix))
    assert np.array_equal(got, vals)


def test_decode_jnp_rejects_int32_unsafe_width():
    with pytest.raises(ValueError, match="too wide"):
        apc.decode_signed_digits_jnp(jnp.zeros((2, 42), jnp.int8), 3)


# ---------------------------------------------------------------------------
# Hypothesis: tiled-vs-untiled MAC equivalence property
# ---------------------------------------------------------------------------

def _check_tiled_untiled(radix, K, k_tile, max_abs, rows, seed):
    width = apc.mac_acc_width(radix, K, max_abs)
    rng = np.random.default_rng(seed)
    x = rng.integers(-max_abs, max_abs + 1, (rows, K))
    w = rng.integers(-1, 2, (rows, K))
    # untiled digits
    arr = jnp.asarray(apc.encode_mac_rows(x, w, radix, width))
    out_u, _ = apc.execute(arr, apc.compile_mac(radix, K, width))
    untiled = apc.decode_mac_acc(np.asarray(out_u), radix, K, width)
    # tiled digits (single-array executor: the equivalence is about the
    # programs, not the pool plumbing)
    tiled_prog = apc.compile_mac_tiled(radix, K, width, k_tile)
    tiled = np.asarray(apc.run_mac_tiled(jnp.asarray(x, jnp.int32),
                                         jnp.asarray(w, jnp.int8),
                                         tiled_prog))
    assert np.array_equal(untiled, tiled)
    assert np.array_equal(untiled, (x * w).sum(axis=1))


try:
    from hypothesis import given, settings, strategies as st_

    @settings(max_examples=12, deadline=None)
    @given(st_.integers(3, 5), st_.integers(2, 8), st_.data())
    def test_tiled_untiled_mac_equivalence_property(radix, K, data):
        k_tile = data.draw(st_.integers(1, K), label="k_tile")
        max_abs = data.draw(st_.integers(1, 4), label="max_abs")
        rows = data.draw(st_.integers(1, 24), label="rows")
        seed = data.draw(st_.integers(0, 2 ** 16), label="seed")
        _check_tiled_untiled(radix, K, k_tile, max_abs, rows, seed)
except ImportError:                     # hypothesis optional: seeded fallback
    @pytest.mark.parametrize("radix,K,k_tile,max_abs,rows,seed", [
        (3, 7, 2, 4, 19, 101), (4, 5, 3, 2, 8, 202), (5, 6, 4, 3, 13, 303),
        (3, 8, 8, 1, 5, 404), (4, 2, 1, 4, 24, 505),
    ])
    def test_tiled_untiled_mac_equivalence_property(radix, K, k_tile,
                                                    max_abs, rows, seed):
        _check_tiled_untiled(radix, K, k_tile, max_abs, rows, seed)
