"""Kernel-variant + VLIW-packing equivalence suite (ISSUE 5).

Contract: the one-hot, one-hot+packed and lanes program kernels are
bit-identical to the gather kernel — digits AND APStats (sets/resets/cycles/
mismatch histogram, including the saturating top bin) — on every program
class, in both interpret (pallas) and compiled (interpret=False, jitted XLA
on CPU) modes; the packing pass serializes every write-slot conflict;
duplicate write/compare columns in one step fall back to the gather body
under the one-hot variants, and the lanes body replays them itself.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro import apc
from repro.core import ap, build_lut_nonblocked
from repro.core import truth_tables as tt
from repro.apc.lower import PackedProgram, pack_steps, resolve_schedule

VARIANTS = ("gather", "onehot", "onehot_packed", "lanes")


def _stats_equal(a: ap.APStats, b: ap.APStats) -> None:
    assert a.sets == b.sets
    assert a.resets == b.resets
    assert a.n_compare_cycles == b.n_compare_cycles
    assert a.n_write_cycles == b.n_write_cycles
    assert np.array_equal(a.mismatch_hist, b.mismatch_hist)


def _run_all_variants(arr, compiled, rows, radix):
    """(digits, APStats) per (variant, interpret) combination."""
    out = {}
    for kv in VARIANTS:
        for interp in (True, False):
            o, tr = apc.execute(arr, compiled, collect_stats=True,
                                kernel_variant=kv, interpret=interp)
            out[(kv, interp)] = (np.asarray(o),
                                 apc.to_ap_stats(tr, compiled, rows, radix))
    return out


def _assert_all_match(results):
    base = results[("gather", True)]
    for key, (digits, stats) in results.items():
        assert np.array_equal(digits, base[0]), f"{key} digits diverge"
        _stats_equal(stats, base[1])


# ---------------------------------------------------------------------------
# Packing-pass structural invariants
# ---------------------------------------------------------------------------

def _cw(cc, key, wc, wv, hist=True):
    from repro.apc.lower import Step
    return Step(keys=(tuple(key),) if cc else (), compare_cols=tuple(cc),
                write_cols=tuple(wc), write_vals=tuple(wv), in_hist=hist)


def test_pack_steps_write_conflicts_do_not_pack():
    """WAW: consecutive steps writing the same column must stay in strictly
    ordered groups (the ISSUE's write-slot-conflict case)."""
    steps = tuple(_cw((0,), (1,), (5,), (v % 3,)) for v in range(6))
    groups = pack_steps(steps, max_pack=8)
    assert [len(g) for g in groups] == [1] * 6          # fully serial
    assert [g[0] for g in groups] == list(range(6))     # order preserved


def test_pack_steps_raw_and_war_serialize():
    # RAW: step 1 compares what step 0 writes
    g = pack_steps((_cw((0,), (1,), (2,), (1,)), _cw((2,), (1,), (3,), (1,))))
    assert len(g) == 2
    # WAR: step 1 writes what step 0 compares
    g = pack_steps((_cw((0,), (1,), (2,), (1,)), _cw((3,), (1,), (0,), (1,))))
    assert len(g) == 2
    # independent columns: one group of 2
    g = pack_steps((_cw((0,), (1,), (2,), (1,)), _cw((1,), (1,), (3,), (1,))))
    assert len(g) == 1 and len(g[0]) == 2


def test_pack_steps_capacity_cap():
    steps = tuple(_cw((c,), (1,), (8 + c,), (1,)) for c in range(8))
    assert [len(g) for g in pack_steps(steps, max_pack=3)] == [3, 3, 2]


def test_packed_program_is_a_padded_permutation():
    compiled = apc.compile_named("max", 3, 6)           # elementwise: packs
    p = compiled.packed()
    assert p.n_groups < compiled.n_steps
    assert p.pack > 1
    assert p.n_slots == p.n_groups * p.pack
    # every original slot appears exactly once; pads are inert no-ops
    occupied = p.key_valid.any(axis=1) | (p.wr_cols >= 0).any(axis=1)
    assert occupied.sum() == compiled.n_steps
    assert not p.hist_flag[~occupied].any()
    assert (p.wr_cols[~occupied] == -1).all()
    # original write-cycle accounting is untouched by packing
    assert compiled.n_write_cycles == compiled.n_steps


def test_elementwise_packs_substantially():
    """Digitwise MVL ops have independent digit positions: the trip count
    must shrink by ~the digit width (capped by max_pack)."""
    compiled = apc.compile_named("max", 3, 8)
    p = compiled.packed()
    assert p.n_groups * 4 <= compiled.n_steps           # >= 4x fewer trips


def test_mul_packing_is_critical_path_bound_and_gated():
    """Carry-ripple programs barely pack (the serial chains are real); the
    resolver must then skip the padded copy rather than inflate slot work."""
    compiled = apc.compile_named("mul", 3, 4)
    p = compiled.packed()
    assert p.n_groups < compiled.n_steps                # repairs overlay
    sched, variant, pack, name = resolve_schedule(compiled, "onehot_packed")
    if p.n_slots > 1.25 * compiled.n_steps:             # inflation gate
        assert pack == 1 and name == "onehot"
        assert sched[0].shape[0] == compiled.n_steps


def test_packed_program_rejects_duplicate_write_cols():
    from repro.apc.lower import CompiledProgram
    dup = CompiledProgram((_cw((0,), (1,), (2, 2), (1, 2)),))
    assert not dup.writes_distinct
    with pytest.raises(ValueError):
        PackedProgram(dup)


# ---------------------------------------------------------------------------
# Bit-exactness: named programs at radix 3/4/5, all variants x interpret
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
    results = _run_all_variants(arr, compiled, rows, radix)
    _assert_all_match(results)
    got = ap.decode_digits(results[("gather", True)][0],
                           list(range(w, 2 * w)), radix)
    want = (a + b if op == "add" else a - b) % radix ** w
    assert np.array_equal(got, want)


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
    results = _run_all_variants(arr, compiled, rows, radix)
    _assert_all_match(results)
    got = ap.decode_digits(results[("gather", True)][0],
                           list(range(3 * w, 5 * w)), radix)
    assert np.array_equal(got, a * b)


@pytest.mark.parametrize("fn", ["max", "min", "modsum", "negate"])
def test_variants_parity_elementwise_and_negate(fn):
    """The program classes where packing actually engages."""
    r, w, rows = 3, 6, 129
    rng = np.random.default_rng(sum(map(ord, fn)))
    a = rng.integers(0, r ** w, rows)
    b = rng.integers(0, r ** w, rows)
    extra = 1 if fn == "negate" else 0
    arr = jnp.asarray(ap.encode_operands(a, b, r, w, extra_cols=extra))
    compiled = apc.compile_named(fn, r, w)
    _assert_all_match(_run_all_variants(arr, compiled, rows, r))


@pytest.mark.parametrize("radix", [3, 4, 5])
def test_variants_parity_mac(radix):
    """The MAC path of the acceptance contract, untiled."""
    K, width, rows = 6, 3, 83
    rng = np.random.default_rng(radix + 100)
    x = rng.integers(-4, 5, (rows, K))
    wt = rng.integers(-1, 2, (rows, K))
    arr = jnp.asarray(apc.encode_mac_rows(x, wt, radix, width))
    compiled = apc.compile_mac(radix, K, width)
    results = _run_all_variants(arr, compiled, rows, radix)
    _assert_all_match(results)
    got = apc.decode_mac_acc(results[("gather", True)][0], radix, K, width)
    assert np.array_equal(got, (x * wt).sum(axis=1))


@pytest.mark.parametrize("kernel_variant",
                         ["onehot", "onehot_packed", "lanes"])
def test_variants_parity_tiled_mac_matmul(kernel_variant):
    """The tiled-MAC serving path (pool + reduction chain) stays bit-exact
    vs the jnp reference and counter-identical vs the gather run."""
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
    stats = {}
    for kv in ("gather", kernel_variant):
        pool = apc.ArrayPool(n_arrays=2, rows=8, cols=64, kernel_variant=kv)
        st = ap.APStats(radix=3)
        y = ternary_matmul_ap(x, packed, scale, radix=3, pool=pool, stats=st)
        assert np.array_equal(np.asarray(y), np.asarray(y_ref))
        stats[kv] = st
    _stats_equal(stats["gather"], stats[kernel_variant])


def test_variants_parity_runtime_graph():
    """DevicePool/Runtime graph route honours the variant knob bit-exactly."""
    rng = np.random.default_rng(9)
    K, width, rows = 12, 4, 21
    tiled = apc.compile_mac_tiled(3, K, width, 4, max_cols=64)
    x = jnp.asarray(rng.integers(-3, 4, (rows, K)), jnp.int32)
    wt = jnp.asarray(rng.integers(-1, 2, (rows, K)), jnp.int8)
    want = np.asarray((np.asarray(x) * np.asarray(wt)).sum(axis=1))
    stats = {}
    for kv in VARIANTS:
        rt = apc.Runtime(apc.ArrayPool(n_arrays=2, rows=8, cols=64),
                         kernel_variant=kv)
        st = ap.APStats(radix=3)
        (digits,) = rt.run_mac_graph([(x, wt, tiled)], stats=st)
        got = np.asarray(apc.decode_signed_digits_jnp(digits, 3))
        assert np.array_equal(got, want)
        stats[kv] = st
    for kv in VARIANTS[1:]:
        _stats_equal(stats["gather"], stats[kv])


# ---------------------------------------------------------------------------
# The lanes body at the served shapes: raw kernel output against gather
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


def _kernel_run(arr, tensors, n_valid, *, block_rows, variant, pack=1,
                interpret):
    from repro.apc.stats import HIST_BINS
    from repro.kernels.tap_pass.kernel import tap_run_program
    out, counts = tap_run_program(
        jnp.asarray(arr), *map(jnp.asarray, tensors), jnp.int32(n_valid),
        block_rows=block_rows, collect_stats=True, hist_bins=HIST_BINS,
        interpret=interpret, variant=variant, pack=pack)
    return np.asarray(out), np.asarray(counts)


@pytest.mark.parametrize("case", [
    "add_r3w20", "mac_k1024_tile", "mac_k1024_reduce", "packed_noop_slots",
    "partial_blocks", "hist_top_bin"])
def test_lanes_kernel_parity(case):
    """The lanes body, interpreted and through the jitted-XLA harness,
    returns the gather body's digits and per-block counters bit for bit
    (every counter, the saturating top histogram bin included)."""
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
    want = _kernel_run(arr, compiled.schedule_tensors, n_valid,
                       block_rows=block_rows, variant="gather",
                       interpret=True)
    tensors, pack = compiled.schedule_tensors, 1
    if case == "packed_noop_slots":
        p = compiled.packed()
        assert p.pack > 1 and p.n_slots > compiled.n_steps   # no-op slots
        tensors, pack = p.schedule_tensors, p.pack
    if case == "hist_top_bin":
        from repro.apc.stats import HIST_BINS
        assert want[1][:, 2 + HIST_BINS - 1].sum() > 0
    for interp in (True, False):
        got = _kernel_run(arr, tensors, n_valid, block_rows=block_rows,
                          variant="lanes", pack=pack, interpret=interp)
        assert np.array_equal(got[0], want[0]), (case, interp)
        assert np.array_equal(got[1], want[1]), (case, interp)


def test_lanes_block_valid_launch_parity():
    """A row-concatenated pool launch (``block_valid``): per-segment
    digits and counters equal the gather body's."""
    compiled = apc.compile_named("add", 3, 20)
    rng = np.random.default_rng(14)
    arr = jnp.asarray(rng.integers(0, 3, (3 * 128, compiled.min_cols)),
                      jnp.int8)
    got = {}
    for kv in ("gather", "lanes"):
        for interp in (True, False):
            pool = apc.ArrayPool(n_arrays=2, rows=128, cols=64,
                                 kernel_variant=kv, interpret=interp)
            out, traced = pool.run(arr, compiled, collect_stats=True,
                                   block_valid=(128, 70, 5))
            got[kv, interp] = (np.asarray(out),
                               np.asarray(traced.block_counts))
    base = got["gather", True]
    assert base[0].shape[0] == 128 + 70 + 5
    for key, (digits, counts) in got.items():
        assert np.array_equal(digits, base[0]), key
        assert np.array_equal(counts, base[1]), key


def test_compiled_path_interpret_false_parity_sharded():
    """interpret=False on CPU (the jitted-XLA harness) through the
    shard_map scaffolding: digits + psummed counters match the oracle."""
    from repro.launch.mesh import make_smoke_mesh
    mesh = make_smoke_mesh()
    r, w, rows = 3, 6, 300
    rng = np.random.default_rng(3)
    a = rng.integers(0, r ** w, rows)
    b = rng.integers(0, r ** w, rows)
    arr = jnp.asarray(ap.encode_operands(a, b, r, w))
    compiled = apc.compile_named("add", r, w)
    out_l, tr_l = apc.execute(arr, compiled, collect_stats=True,
                              kernel_variant="gather", interpret=True)
    for kv in VARIANTS:
        out_s, tr_s = apc.execute_sharded(arr, compiled, mesh,
                                          collect_stats=True, block_rows=128,
                                          kernel_variant=kv, interpret=False)
        assert np.array_equal(np.asarray(out_l), np.asarray(out_s))
        _stats_equal(apc.to_ap_stats(tr_l, compiled, rows, r),
                     apc.to_ap_stats(tr_s, compiled, rows, r))


def test_dup_write_cols_fall_back_to_gather_bit_exact():
    """Steps with duplicate write columns keep serial write semantics: the
    resolver must route every one-hot request to the gather body (the
    lanes body replays write columns one by one itself), and the result
    must equal the legacy jnp schedule oracle."""
    from repro.kernels.tap_pass.ref import apply_schedule
    prog = (apc.CompareWrite(compare_cols=(0,), key=(1,),
                             write_cols=(2, 2), write_vals=(1, 2)),
            apc.CompareWrite(compare_cols=(1, 1), key=(0, 0),
                             write_cols=(3,), write_vals=(2,)),)
    compiled = apc.compile_program(prog)
    assert not compiled.writes_distinct and not compiled.compares_distinct
    for kv in VARIANTS:
        sched, variant, pack, name = resolve_schedule(compiled, kv)
        want = "lanes" if kv == "lanes" else "gather"
        assert (variant, pack, name) == (want, 1, want)
    rng = np.random.default_rng(8)
    arr = jnp.asarray(rng.integers(0, 3, (64, 4)), jnp.int8)
    want = np.asarray(apply_schedule(arr, compiled.as_tap_steps()))
    for kv in VARIANTS:
        for interp in (True, False):
            out, _ = apc.execute(arr, compiled, kernel_variant=kv,
                                 interpret=interp)
            assert np.array_equal(np.asarray(out), want)


def test_runtime_route_rejects_unhonored_knobs():
    """The runtime= route executes with the Runtime's own knobs; explicit
    per-call knobs that differ (including vs an unset None) must raise
    instead of being silently dropped."""
    from repro.kernels.ternary_matmul.ap import ternary_matmul_ap
    from repro.kernels.ternary_matmul.ops import quantize_and_pack
    import jax
    w = jax.random.normal(jax.random.PRNGKey(0), (12, 2), jnp.float32) * .05
    packed, scale = quantize_and_pack(w)
    x = jnp.asarray(np.ones((2, 12)), jnp.float32)
    rt = apc.Runtime(apc.ArrayPool(n_arrays=1, rows=8, cols=64),
                     kernel_variant="gather")
    with pytest.raises(ValueError, match="kernel_variant"):
        ternary_matmul_ap(x, packed, scale, runtime=rt,
                          kernel_variant="onehot")
    with pytest.raises(ValueError, match="unroll"):
        ternary_matmul_ap(x, packed, scale, runtime=rt, unroll=8)
    with pytest.raises(ValueError, match="interpret"):
        ternary_matmul_ap(x, packed, scale, runtime=rt, interpret=False)
    # matching knobs pass through
    y = ternary_matmul_ap(x, packed, scale, runtime=rt,
                          kernel_variant="gather")
    assert y.shape == (2, 2)
    # explicit values that restate the backend default of an unconfigured
    # Runtime stay compatible (the pre-knob API's interpret=True callers)
    rt_default = apc.Runtime(apc.ArrayPool(n_arrays=1, rows=8, cols=64))
    from repro.kernels.tap_pass.kernel import resolve_interpret
    y = ternary_matmul_ap(x, packed, scale, runtime=rt_default,
                          interpret=resolve_interpret(None))
    assert y.shape == (2, 2)


def test_short_schedule_env_lever_does_not_reach_pallas_compiled(
        monkeypatch):
    """REPRO_AP_INTERPRET=0 must not crash the short-schedule (unrolled
    pallas) path on a CPU host — it has no compiled pallas lowering, so the
    lever applies only to the program kernel there."""
    from repro.kernels.tap_pass.ops import tap_apply_lut
    from repro.core.nonblocked import build_lut_nonblocked
    lut = build_lut_nonblocked(tt.full_adder(3))
    rng = np.random.default_rng(2)
    arr = jnp.asarray(rng.integers(0, 3, (64, 3)), jnp.int8)
    want = np.asarray(tap_apply_lut(arr, lut, (0, 1, 2), block_rows=64))
    monkeypatch.setenv("REPRO_AP_INTERPRET", "0")
    got = np.asarray(tap_apply_lut(arr, lut, (0, 1, 2), block_rows=64))
    assert np.array_equal(got, want)
    monkeypatch.delenv("REPRO_AP_INTERPRET")
    # an EXPLICIT interpret=False is honored: the short schedule routes
    # through the program kernel's compiled XLA harness, same digits
    got = np.asarray(tap_apply_lut(arr, lut, (0, 1, 2), block_rows=64,
                                   interpret=False))
    assert np.array_equal(got, want)


def test_unroll_knob_values_are_bit_exact():
    r, w, rows = 3, 5, 77
    rng = np.random.default_rng(4)
    a = rng.integers(0, r ** w, rows)
    b = rng.integers(0, r ** w, rows)
    arr = jnp.asarray(ap.encode_operands(a, b, r, w))
    compiled = apc.compile_named("add", r, w)
    base, tr = apc.execute(arr, compiled, collect_stats=True, unroll=1)
    s0 = apc.to_ap_stats(tr, compiled, rows, r)
    for unroll in (2, 4, 8):
        out, tr = apc.execute(arr, compiled, collect_stats=True,
                              unroll=unroll)
        assert np.array_equal(np.asarray(out), np.asarray(base))
        _stats_equal(s0, apc.to_ap_stats(tr, compiled, rows, r))
    with pytest.raises(ValueError):
        apc.execute(arr, compiled, unroll=0)
    with pytest.raises(ValueError):
        apc.execute(arr, compiled, kernel_variant="vliw9000")


# ---------------------------------------------------------------------------
# Cache bounds + stats exposure (ISSUE 5 satellite)
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
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st
    from repro.apc.lower import Step, CompiledProgram

    N_COLS = 6

    @st.composite
    def schedules(draw):
        radix = draw(st.integers(3, 5))
        n_steps = draw(st.integers(2, 12))
        steps = []
        for _ in range(n_steps):
            cc = tuple(sorted(draw(st.sets(st.integers(0, N_COLS - 1),
                                           max_size=3))))
            wc = tuple(sorted(draw(st.sets(st.integers(0, N_COLS - 1),
                                           min_size=1, max_size=2))))
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
        base, tr = apc.execute(arr, compiled, collect_stats=True,
                               kernel_variant="gather", interpret=True)
        s0 = apc.to_ap_stats(tr, compiled, rows, radix)
        for kv in ("onehot", "onehot_packed", "lanes"):
            for interp in (True, False):
                out, tr = apc.execute(arr, compiled, collect_stats=True,
                                      kernel_variant=kv, interpret=interp)
                assert np.array_equal(np.asarray(out), np.asarray(base))
                _stats_equal(s0, apc.to_ap_stats(tr, compiled, rows, radix))

    prop()
