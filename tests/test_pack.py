"""Program-kernel equivalence suite: the one kernel body against the oracles.

Contract: the program kernel (the ``lanes`` body) returns the replay
oracle's digits AND per-block counters (sets/resets/mismatch histogram,
including the saturating top bin) on every program class, in both
interpret (pallas) and compiled (``interpret=False``, jitted XLA on CPU)
modes.  The oracles are :func:`_oracle` below — a numpy step-by-step
replay of a :class:`~repro.apc.lower.Step` schedule with per-block
counters — the ``kernels/tap_pass/ref.py`` digit replay, and the
``core.ap`` pass-by-pass drivers.  Steps with duplicate write or compare
columns replay in order, one column after another.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro import apc
from repro.apc.lower import CompiledProgram, Step
from repro.apc.stats import HIST_BINS
from repro.core import ap, build_lut_nonblocked
from repro.core import truth_tables as tt
from repro.kernels.tap_pass.ref import DONT_CARE

MODES = (True, False)            # interpret: pallas interpreter, XLA harness


def _stats_equal(a: ap.APStats, b: ap.APStats) -> None:
    assert a.sets == b.sets
    assert a.resets == b.resets
    assert a.n_compare_cycles == b.n_compare_cycles
    assert a.n_write_cycles == b.n_write_cycles
    assert np.array_equal(a.mismatch_hist, b.mismatch_hist)


def _oracle(arr, compiled: CompiledProgram, n_valid: int | None = None,
            block_rows: int | None = None):
    """Step-by-step numpy replay: ``(digits, counts)`` with ``counts`` the
    per-block (n_blocks, 2 + HIST_BINS) [sets, resets, hist...] counters.
    Rows at or past ``n_valid`` are neither written nor counted."""
    d = np.array(arr, np.int64)
    rows = d.shape[0]
    n_valid = rows if n_valid is None else n_valid
    block_rows = block_rows or max(1, rows)
    ok = np.arange(rows) < n_valid
    blk = np.arange(rows) // block_rows
    counts = np.zeros((max(1, -(-rows // block_rows)), 2 + HIST_BINS),
                      np.int64)
    for st in compiled.steps:
        tag = ok.copy()
        if st.keys:
            tag[:] = False
            for key in st.keys:
                mm = np.zeros(rows, np.int64)
                for c, k in zip(st.compare_cols, key):
                    mm += (d[:, c] != k) & (d[:, c] != DONT_CARE)
                tag |= mm == 0
                if st.in_hist:
                    np.add.at(counts, (blk[ok], 2 + np.minimum(
                        mm[ok], HIST_BINS - 1)), 1)
            tag &= ok
        for c, v in zip(st.write_cols, st.write_vals):
            old = d[:, c].copy()
            changed = tag & (old != v)
            np.add.at(counts, (blk[changed], 0), 1)
            np.add.at(counts, (blk[changed & (old != DONT_CARE)], 1), 1)
            d[changed, c] = v
    return d.astype(np.int8), counts


def _oracle_stats(counts, compiled, rows, radix) -> ap.APStats:
    return apc.to_ap_stats(apc.TracedStats(jnp.asarray(counts, jnp.int32)),
                           compiled, rows, radix)


def _check_execute(arr, compiled, radix):
    """Run ``apc.execute`` in both modes and hold each to the oracle's
    digits and counters; returns the oracle's digits and APStats."""
    rows = arr.shape[0]
    want, counts = _oracle(arr, compiled)
    want_stats = _oracle_stats(counts, compiled, rows, radix)
    for interp in MODES:
        out, tr = apc.execute(arr, compiled, collect_stats=True,
                              interpret=interp)
        assert np.array_equal(np.asarray(out), want), interp
        _stats_equal(apc.to_ap_stats(tr, compiled, rows, radix), want_stats)
    return want, want_stats


def _kernel_run(arr, tensors, n_valid, *, block_rows, interpret):
    from repro.kernels.tap_pass.kernel import tap_run_program
    out, counts = tap_run_program(
        jnp.asarray(arr), *map(jnp.asarray, tensors), jnp.int32(n_valid),
        block_rows=block_rows, collect_stats=True, hist_bins=HIST_BINS,
        interpret=interpret)
    return np.asarray(out), np.asarray(counts)


# ---------------------------------------------------------------------------
# Bit-exactness: named programs at radix 3/4/5, both modes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("radix", [3, 4, 5])
@pytest.mark.parametrize("op", ["add", "sub"])
def test_variants_parity_addsub(radix, op):
    w, rows = 4, 157
    rng = np.random.default_rng(radix * 11 + len(op))
    a = rng.integers(0, radix ** w, rows)
    b = rng.integers(0, radix ** w, rows)
    arr = jnp.asarray(ap.encode_operands(a, b, radix, w))
    compiled = apc.compile_named(op, radix, w)
    digits, stats = _check_execute(arr, compiled, radix)
    got = ap.decode_digits(digits, list(range(w, 2 * w)), radix)
    want = (a + b if op == "add" else a - b) % radix ** w
    assert np.array_equal(got, want)
    # the core.ap pass-by-pass replay writes and charges the same
    lut = build_lut_nonblocked(
        tt.full_adder(radix) if op == "add" else tt.full_subtractor(radix))
    driver = ap.ripple_add if op == "add" else ap.ripple_sub
    so = ap.APStats(radix=radix)
    assert np.array_equal(np.asarray(driver(arr, lut, w, 2 * w, stats=so)),
                          digits)
    _stats_equal(so, stats)


@pytest.mark.parametrize("radix", [3, 4, 5])
def test_variants_parity_mul(radix):
    w, rows = 2 if radix == 5 else 3, 61
    rng = np.random.default_rng(radix)
    a = rng.integers(0, radix ** w, rows)
    b = rng.integers(0, radix ** w, rows)
    arr = np.zeros((rows, 5 * w + 1), np.int8)
    for i in range(w):
        arr[:, i] = arr[:, w + i] = (a // radix ** i) % radix
        arr[:, 2 * w + i] = (b // radix ** i) % radix
    arr = jnp.asarray(arr)
    compiled = apc.compile_named("mul", radix, w)
    digits, _ = _check_execute(arr, compiled, radix)
    got = ap.decode_digits(digits, list(range(3 * w, 5 * w)), radix)
    assert np.array_equal(got, a * b)


@pytest.mark.parametrize("fn", ["max", "min", "modsum", "negate"])
def test_variants_parity_elementwise_and_negate(fn):
    """The digitwise programs and the radix complement."""
    r, w, rows = 3, 6, 129
    rng = np.random.default_rng(sum(map(ord, fn)))
    a = rng.integers(0, r ** w, rows)
    b = rng.integers(0, r ** w, rows)
    extra = 1 if fn == "negate" else 0
    arr = jnp.asarray(ap.encode_operands(a, b, r, w, extra_cols=extra))
    compiled = apc.compile_named(fn, r, w)
    digits, _ = _check_execute(arr, compiled, r)
    if fn == "negate":
        got = ap.decode_digits(digits, list(range(w, 2 * w)), r)
        assert np.array_equal(got, (-a) % r ** w)


@pytest.mark.parametrize("radix", [3, 4, 5])
def test_variants_parity_mac(radix):
    """The MAC path of the acceptance contract, untiled."""
    K, width, rows = 6, 3, 83
    rng = np.random.default_rng(radix + 100)
    x = rng.integers(-4, 5, (rows, K))
    wt = rng.integers(-1, 2, (rows, K))
    arr = jnp.asarray(apc.encode_mac_rows(x, wt, radix, width))
    compiled = apc.compile_mac(radix, K, width)
    digits, _ = _check_execute(arr, compiled, radix)
    got = apc.decode_mac_acc(digits, radix, K, width)
    assert np.array_equal(got, (x * wt).sum(axis=1))


@pytest.mark.parametrize("radix", [3, 4, 5])
def test_variants_parity_tiled_mac_matmul(radix):
    """The tiled-MAC serving path (pool + reduction chain) is bit-exact vs
    the jnp reference and counter-identical across the two modes."""
    from repro.kernels.ternary_matmul.ap import ternary_matmul_ap
    from repro.kernels.ternary_matmul.ops import quantize_and_pack
    from repro.kernels.ternary_matmul.ref import ternary_matmul_ref
    import jax
    rng = np.random.default_rng(5)
    m, k, n = 3, 24, 3
    w = jax.random.normal(jax.random.PRNGKey(0), (k, n), jnp.float32) * .05
    packed, scale = quantize_and_pack(w)
    x = jnp.asarray(rng.integers(-3, 4, (m, k)), jnp.float32)
    y_ref = ternary_matmul_ref(x, packed, scale)
    stats = []
    for interp in MODES:
        pool = apc.ArrayPool(n_arrays=2, rows=8, cols=64, interpret=interp)
        st = ap.APStats(radix=radix)
        y = ternary_matmul_ap(x, packed, scale, radix=radix, pool=pool,
                              stats=st)
        assert np.array_equal(np.asarray(y), np.asarray(y_ref))
        stats.append(st)
    assert stats[0].n_write_cycles > 0
    _stats_equal(*stats)


def test_variants_parity_runtime_graph():
    """The DevicePool/Runtime graph route, in both modes, charges what the
    single-array tiled MAC charges and decodes to the dot products."""
    rng = np.random.default_rng(9)
    K, width, rows = 12, 4, 21
    tiled = apc.compile_mac_tiled(3, K, width, 4, max_cols=64)
    x = jnp.asarray(rng.integers(-3, 4, (rows, K)), jnp.int32)
    wt = jnp.asarray(rng.integers(-1, 2, (rows, K)), jnp.int8)
    want = np.asarray((np.asarray(x) * np.asarray(wt)).sum(axis=1))
    base = ap.APStats(radix=3)
    got = apc.run_mac_tiled(x, wt, tiled, stats=base)
    assert np.array_equal(np.asarray(got), want)
    for interp in MODES:
        rt = apc.Runtime(apc.ArrayPool(n_arrays=2, rows=8, cols=64),
                         interpret=interp)
        st = ap.APStats(radix=3)
        (digits,) = rt.run_mac_graph([(x, wt, tiled)], stats=st)
        got = np.asarray(apc.decode_signed_digits_jnp(digits, 3))
        assert np.array_equal(got, want)
        _stats_equal(base, st)


# ---------------------------------------------------------------------------
# The kernel at the served shapes: raw output against the oracle
# ---------------------------------------------------------------------------

def _served_tiled(K: int):
    """The TiledMac the served qwen3-0.6b MLP builds for a K-wide
    projection (x_levels 7, radix 3) on a 256-column bank array."""
    from repro.apc.mac import mac_acc_width
    from repro.kernels.ternary_matmul.ap import default_k_tile
    width = mac_acc_width(3, K, 7)
    return apc.compile_mac_tiled(
        3, K, width, min(default_k_tile(256, width), K), max_cols=256)


def _wide_compare_program():
    """Steps comparing 9 columns, so mismatch counts reach past the
    histogram's top bin (HIST_BINS - 1), which must saturate."""
    return apc.compile_program((
        apc.CompareWrite(compare_cols=tuple(range(9)), key=(0,) * 9,
                         write_cols=(9,), write_vals=(1,),
                         count_mismatch=True),
        apc.CompareWrite(compare_cols=tuple(range(1, 10)), key=(1,) * 9,
                         write_cols=(0, 10), write_vals=(2, 0),
                         count_mismatch=True),
        apc.CompareWrite(compare_cols=(10,), key=(0,), write_cols=(3,),
                         write_vals=(2,))))


def _with_noop_slots(compiled: CompiledProgram) -> tuple[np.ndarray, ...]:
    """``compiled``'s schedule tensors with a no-op slot (no valid key,
    every write column -1) after every step."""
    out = []
    for t in compiled.schedule_tensors:
        pad = np.full_like(t, -1) if t.dtype == np.int32 else \
            np.zeros_like(t)
        out.append(np.stack([t, pad], axis=1).reshape(-1, *t.shape[1:]))
    return tuple(out)


@pytest.mark.parametrize("case", [
    "add_r3w20", "mac_k1024_tile", "mac_k1024_reduce", "packed_noop_slots",
    "partial_blocks", "hist_top_bin"])
def test_lanes_kernel_parity(case):
    """The kernel, interpreted and through the jitted-XLA harness, returns
    the oracle's digits and per-block counters bit for bit (every counter,
    the saturating top histogram bin included); no-op slots interleaved
    into the schedule change nothing."""
    rows, block_rows, n_valid = 256, 128, 256
    if case in ("add_r3w20", "partial_blocks"):
        compiled = apc.compile_named("add", 3, 20)
        if case == "partial_blocks":        # 300 % 128 != 0, 2 lane rows
            rows, block_rows, n_valid = 512, 256, 300
    elif case == "mac_k1024_tile":
        compiled = _served_tiled(1024).programs[0]
        rows, block_rows, n_valid = 128, 128, 97
    elif case == "mac_k1024_reduce":
        compiled = _served_tiled(1024).reduce_programs[0]
        rows, block_rows, n_valid = 128, 128, 128
    elif case == "packed_noop_slots":
        compiled = apc.compile_named("negate", 3, 6)
    else:
        compiled = _wide_compare_program()
    if case == "mac_k1024_tile":
        assert compiled.min_cols == 250 and compiled.n_steps > 9000
    rng = np.random.default_rng(sum(map(ord, case)))
    arr = rng.integers(-1, 3, (rows, compiled.min_cols)).astype(np.int8)
    want = _oracle(arr, compiled, n_valid, block_rows)
    tensors = compiled.schedule_tensors
    if case == "packed_noop_slots":
        tensors = _with_noop_slots(compiled)
        assert len(tensors[0]) == 2 * compiled.n_steps
    if case == "hist_top_bin":
        assert want[1][:, 2 + HIST_BINS - 1].sum() > 0
    for interp in MODES:
        got = _kernel_run(arr, tensors, n_valid, block_rows=block_rows,
                          interpret=interp)
        assert np.array_equal(got[0], want[0]), (case, interp)
        assert np.array_equal(got[1], want[1]), (case, interp)


def _random_program(rng, n_steps: int, n_cols: int, radix: int = 3
                    ) -> CompiledProgram:
    """``n_steps`` random steps over ``n_cols`` columns: up to 3 compare
    and 2 write columns (repeats allowed), up to 2 keys, some
    unconditional writes and some steps out of the histogram."""
    steps = []
    for _ in range(n_steps):
        cc = tuple(int(c) for c in rng.integers(0, n_cols,
                                                 rng.integers(0, 4)))
        wc = tuple(int(c) for c in rng.integers(0, n_cols,
                                                 rng.integers(1, 3)))
        keys = tuple(tuple(int(v) for v in rng.integers(0, radix, len(cc)))
                     for _ in range(rng.integers(1, 3))) if cc else ()
        wv = tuple(int(v) for v in rng.integers(0, radix, len(wc)))
        steps.append(Step(keys=keys, compare_cols=cc, write_cols=wc,
                          write_vals=wv, in_hist=bool(rng.integers(0, 4))))
    return CompiledProgram(tuple(steps))


@pytest.mark.parametrize("n_steps", [1023, 1024, 1025, 2049])
def test_schedule_chunk_boundaries(n_steps):
    """Programs one slot short of, at, one past and one past twice the
    pallas kernel's schedule chunk: each chunk streams whole slots and the
    rounded-up tail is no-op padding, so digits and counters match the
    oracle."""
    from repro.kernels.tap_pass.kernel import SCHED_CHUNK
    assert SCHED_CHUNK == 1024
    rng = np.random.default_rng(n_steps)
    compiled = _random_program(rng, n_steps, n_cols=7)
    assert compiled.n_steps == n_steps
    rows, block_rows, n_valid = 96, 32, 90
    arr = rng.integers(-1, 3, (rows, 7)).astype(np.int8)
    want = _oracle(arr, compiled, n_valid, block_rows)
    for interp in MODES:
        got = _kernel_run(arr, compiled.schedule_tensors, n_valid,
                          block_rows=block_rows, interpret=interp)
        assert np.array_equal(got[0], want[0]), interp
        assert np.array_equal(got[1], want[1]), interp


@pytest.mark.parametrize("block_rows", [128, 1024, 4096])
def test_interpret_modes_bit_identical(block_rows):
    """The two CPU routes of the one body — the pallas interpreter and the
    jitted-XLA harness — give identical digits and per-block counters on
    two and a half blocks, the last one partial, and both match the
    oracle."""
    compiled = apc.compile_named("add", 3, 20)
    rows = 3 * block_rows
    n_valid = 2 * block_rows + block_rows // 2 + 3
    rng = np.random.default_rng(block_rows)
    arr = rng.integers(-1, 3, (rows, compiled.min_cols)).astype(np.int8)
    pallas = _kernel_run(arr, compiled.schedule_tensors, n_valid,
                         block_rows=block_rows, interpret=True)
    xla = _kernel_run(arr, compiled.schedule_tensors, n_valid,
                      block_rows=block_rows, interpret=False)
    assert pallas[1].shape == (3, 2 + HIST_BINS)
    assert np.array_equal(pallas[0], xla[0])
    assert np.array_equal(pallas[1], xla[1])
    want = _oracle(arr, compiled, n_valid, block_rows)
    assert np.array_equal(pallas[0], want[0])
    assert np.array_equal(pallas[1], want[1])


def test_lanes_block_valid_launch_parity():
    """A row-concatenated pool launch (``block_valid``): per-segment
    digits and counters equal the oracle's for each segment alone."""
    compiled = apc.compile_named("add", 3, 20)
    rng = np.random.default_rng(14)
    arr = rng.integers(0, 3, (3 * 128, compiled.min_cols)).astype(np.int8)
    valid = (128, 70, 5)
    segs = [_oracle(arr[b * 128:(b + 1) * 128], compiled, v, 128)
            for b, v in enumerate(valid)]
    want_digits = np.concatenate([d[:v] for (d, _), v in zip(segs, valid)])
    want_counts = np.concatenate([c for _, c in segs])
    for interp in MODES:
        pool = apc.ArrayPool(n_arrays=2, rows=128, cols=64,
                             interpret=interp)
        out, traced = pool.run(jnp.asarray(arr), compiled,
                               collect_stats=True, block_valid=valid)
        assert np.array_equal(np.asarray(out), want_digits), interp
        assert np.array_equal(np.asarray(traced.block_counts),
                              want_counts), interp


def test_compiled_path_interpret_false_parity_sharded():
    """Both modes through the shard_map scaffolding: digits + psummed
    counters match the oracle."""
    from repro.launch.mesh import make_smoke_mesh
    mesh = make_smoke_mesh()
    r, w, rows = 3, 6, 300
    rng = np.random.default_rng(3)
    a = rng.integers(0, r ** w, rows)
    b = rng.integers(0, r ** w, rows)
    arr = jnp.asarray(ap.encode_operands(a, b, r, w))
    compiled = apc.compile_named("add", r, w)
    want, counts = _oracle(arr, compiled)
    want_stats = _oracle_stats(counts, compiled, rows, r)
    for interp in MODES:
        out_s, tr_s = apc.execute_sharded(arr, compiled, mesh,
                                          collect_stats=True, block_rows=128,
                                          interpret=interp)
        assert np.array_equal(np.asarray(out_s), want)
        _stats_equal(apc.to_ap_stats(tr_s, compiled, rows, r), want_stats)


def test_dup_cols_replay_in_order_bit_exact():
    """Steps with duplicate write columns keep serial write semantics (the
    last value wins, every change is charged) and duplicate compare
    columns count one mismatch per position: the kernel replays both in
    order and equals the jnp schedule oracle and the counter oracle."""
    from repro.kernels.tap_pass.ref import apply_schedule
    prog = (apc.CompareWrite(compare_cols=(0,), key=(1,),
                             write_cols=(2, 2), write_vals=(1, 2)),
            apc.CompareWrite(compare_cols=(1, 1), key=(0, 0),
                             write_cols=(3,), write_vals=(2,)),)
    compiled = apc.compile_program(prog)
    rng = np.random.default_rng(8)
    arr = jnp.asarray(rng.integers(0, 3, (64, 4)), jnp.int8)
    want = np.asarray(apply_schedule(arr, compiled.as_tap_steps()))
    assert np.array_equal(_check_execute(arr, compiled, 3)[0], want)


def test_runtime_route_rejects_unhonored_knobs():
    """The runtime= route executes with the Runtime's own ``interpret``;
    an explicit per-call value that differs (including vs an unset None)
    must raise instead of being silently dropped."""
    from repro.kernels.ternary_matmul.ap import ternary_matmul_ap
    from repro.kernels.ternary_matmul.ops import quantize_and_pack
    import jax
    w = jax.random.normal(jax.random.PRNGKey(0), (12, 2), jnp.float32) * .05
    packed, scale = quantize_and_pack(w)
    x = jnp.asarray(np.ones((2, 12)), jnp.float32)
    rt = apc.Runtime(apc.ArrayPool(n_arrays=1, rows=8, cols=64),
                     interpret=True)
    with pytest.raises(ValueError, match="interpret"):
        ternary_matmul_ap(x, packed, scale, runtime=rt, interpret=False)
    # a matching value passes through
    y = ternary_matmul_ap(x, packed, scale, runtime=rt, interpret=True)
    assert y.shape == (2, 2)
    # explicit values that restate the backend default of an unconfigured
    # Runtime stay compatible
    rt_default = apc.Runtime(apc.ArrayPool(n_arrays=1, rows=8, cols=64))
    from repro.kernels.tap_pass.kernel import resolve_interpret
    y = ternary_matmul_ap(x, packed, scale, runtime=rt_default,
                          interpret=resolve_interpret(None))
    assert y.shape == (2, 2)
    with pytest.raises(ValueError, match="interpret"):
        ternary_matmul_ap(x, packed, scale, runtime=rt_default,
                          interpret=not resolve_interpret(None))


# ---------------------------------------------------------------------------
# Cache bounds + stats exposure
# ---------------------------------------------------------------------------

def test_compile_caches_all_bounded_with_stats():
    stats = apc.cache_stats()
    assert {"lut_nonblocked", "lut_blocked", "compile_steps",
            "compile_named", "compile_mac", "compile_mac_reduce",
            "compile_mac_tiled"} <= set(stats)
    for name, info in stats.items():
        assert info["maxsize"] is not None, f"{name} cache is unbounded"
        assert info["currsize"] <= info["maxsize"]
    apc.compile_named("add", 3, 4)
    before = apc.cache_stats()["compile_named"]["hits"]
    apc.compile_named("add", 3, 4)
    assert apc.cache_stats()["compile_named"]["hits"] == before + 1


def test_ap_serve_context_exposes_cache_stats():
    ctx = apc.APServeContext(
        apc.Runtime(apc.ArrayPool(n_arrays=1, rows=8, cols=64)))
    lin = apc.APLinear.from_dense(np.ones((6, 2), np.float32))
    lin(jnp.ones((2, 6), jnp.float32), ctx)
    cs = ctx.cache_stats()
    assert cs["pool_schedules"] >= 1
    assert cs["pool_schedules"] <= cs["pool_schedules_max"]
    assert cs["compile"]["compile_mac_tiled"]["currsize"] >= 1


# ---------------------------------------------------------------------------
# Hypothesis: random schedules (with conflicts) replay bit-identically
# ---------------------------------------------------------------------------

def test_random_schedules_packed_parity_hypothesis():
    """Random schedules — repeated columns, read-after-write chains,
    unconditional writes, stored don't-cares — replay as the oracle does
    in both modes."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    N_COLS = 6

    @st.composite
    def schedules(draw):
        radix = draw(st.integers(3, 5))
        n_steps = draw(st.integers(2, 12))
        steps = []
        for _ in range(n_steps):
            cc = tuple(draw(st.lists(st.integers(0, N_COLS - 1),
                                     max_size=3)))
            wc = tuple(draw(st.lists(st.integers(0, N_COLS - 1),
                                     min_size=1, max_size=2)))
            keys = tuple(
                tuple(draw(st.integers(0, radix - 1)) for _ in cc)
                for _ in range(draw(st.integers(1, 2)))) if cc else ()
            wv = tuple(draw(st.integers(0, radix - 1)) for _ in wc)
            steps.append(Step(keys=keys, compare_cols=cc, write_cols=wc,
                              write_vals=wv,
                              in_hist=draw(st.booleans()) and bool(cc)))
        return radix, tuple(steps)

    @given(schedules(), st.integers(0, 2 ** 31 - 1))
    @settings(max_examples=40, deadline=None)
    def prop(sched, seed):
        radix, steps = sched
        compiled = CompiledProgram(steps)
        rng = np.random.default_rng(seed)
        rows = int(rng.integers(1, 40))
        # include stored don't-cares: they match every key
        arr = jnp.asarray(rng.integers(-1, radix, (rows, N_COLS)), jnp.int8)
        _check_execute(arr, compiled, radix)

    prop()
