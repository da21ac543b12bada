"""Work counts from schedules agree with the program's own APStats."""
import numpy as np
import pytest

import work
from reference import tap_add


def _run(prog, arr, rows_per_block=64):
    import jax.numpy as jnp
    from repro.apc.pool import ArrayPool, run_pooled
    from repro.core.ap import APStats
    st = APStats(radix=3)
    pool = ArrayPool(n_arrays=2, rows=rows_per_block, cols=256)
    run_pooled(jnp.asarray(arr), prog, pool, stats=st).block_until_ready()
    return st, pool


def test_add_r3_w20_work_matches_apstats():
    from repro import apc
    prog = apc.compile_named("add", 3, 20)
    rows = 150
    rng = np.random.default_rng(0)
    arr = tap_add.encode(rng.integers(0, 3 ** 20, rows),
                         rng.integers(0, 3 ** 20, rows), 3, 20)
    st, pool = _run(prog, arr)
    assert work.compare_cycles(prog) == st.n_compare_cycles == 420
    assert len(prog.steps) == st.n_write_cycles == 421
    assert rows * work.hist_row_compares(prog) == int(st.mismatch_hist.sum())
    # the paper's LUT: a 3-digit compare per pass, its write digits, and the
    # write that clears the carry
    per_digit = sum(3 + len(wc) for _, wc, _ in tap_add.full_adder_passes(3))
    assert work.ops_per_row(prog) == 20 * per_digit + 1
    w = work.run_work(prog, rows, pool.rows, arr.shape[1])
    assert w["launches"] == 3
    assert w["ops"] == rows * work.ops_per_row(prog)
    assert w["bytes"] == 3 * (2 * 64 * 41 + work.schedule_bytes(prog))


def test_mac_tile_work_matches_apstats():
    import jax.numpy as jnp
    from repro.apc.mac import (compile_mac_tiled, encode_mac_rows_jnp,
                               mac_acc_width)
    K, R = 64, 100
    width = mac_acc_width(3, K, 7)
    tiled = compile_mac_tiled(3, K, width, 16, max_cols=256)
    prog = tiled.programs[0]
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.integers(-7, 8, (R, 16)), jnp.int32)
    w = jnp.asarray(rng.integers(-1, 2, (R, 16)), jnp.int8)
    arr = encode_mac_rows_jnp(x, w, 3, width)
    st, _ = _run(prog, np.asarray(arr))
    assert work.compare_cycles(prog) == st.n_compare_cycles
    assert len(prog.steps) == st.n_write_cycles
    assert R * work.hist_row_compares(prog) == int(st.mismatch_hist.sum())
    assert work.ops_per_row(prog) > st.n_compare_cycles


def test_roofline_share_takes_the_longer_bound():
    peaks = {"int8_ops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.roofline_share({"ops": 200, "bytes": 10}, 4.0, peaks) == 50.0
    assert work.roofline_share({"ops": 10, "bytes": 30}, 6.0, peaks) == 50.0
    assert work.roofline_share({"ops": 10, "bytes": 30}, 0.0, peaks) is None
    assert work.roofline_share({"ops": 10, "bytes": 30}, 1.0, None) is None


def test_float_flops_of_a_token():
    # q, k, v and o projections of qwen3-0.6b's attention plus 8 positions
    assert work.attention_flops(1024, 16, 8, 128, 8) == \
        2 * 1024 * 128 * (32 + 16) + 4 * 16 * 128 * 8
    assert work.head_flops(1024, 151936) == 2 * 1024 * 151936


@pytest.mark.parametrize("spec,want", [
    ({"values": [3, 1]}, [3, 1]),
    ({"range": 3}, [0, 1, 2]),
    ({"log_uniform_int": {"lo": 64, "hi": 16384, "n": 2}}, [256, 4096]),
])
def test_traffic_fields(spec, want):
    import loadgen
    assert loadgen.field_values(spec) == want


def test_every_seed_sends_the_same_jobs_in_another_order():
    import loadgen
    traffic = {"job": {"a": {"values": [1, 2, 3]}, "b": {"range": 2}}}
    one, two = loadgen.job_cycle(traffic, 1), loadgen.job_cycle(traffic, 2)
    key = lambda js: sorted((j["a"], j["b"]) for j in js)  # noqa: E731
    assert key(one) == key(two) and len(one) == 6
    assert one != two
    assert loadgen.job_cycle(traffic, 1) == one


def test_logit_gaps_and_greedy_misses():
    from reference import qwen3
    ref = np.array([[1.0, 2.0, 0.0], [0.0, 3.0, 4.0]])
    assert qwen3.rel_gaps(ref, ref) == [0.0, 0.0]
    got = ref + np.array([[0.0, 0.0, 0.5], [0.0, 0.0, 0.0]])
    assert qwen3.rel_gaps(got, ref)[0] == pytest.approx(0.5 / np.sqrt(5))
    assert qwen3.greedy_misses(ref, [1, 2], [0, 1]) == 0
    assert qwen3.greedy_misses(ref, [0, 2], [0, 1]) == 1


def test_a_level_is_taken_only_at_a_tie():
    import jax.numpy as jnp
    from reference import qwen3
    v = jnp.asarray([[2.49, 2.3, 2.49, 0.0, 2.51]])
    own = jnp.asarray([[2, 2, 2, 0, 3]])
    given = jnp.asarray([[3, 3, 4, qwen3.NOT_GIVEN, 2]])
    took, not_taken = qwen3.take_ties(v, own, given, 0.015625)
    # a tie one step away is taken; far from the boundary, two steps away,
    # or not given, the reference keeps its own
    assert took.tolist() == [[3, 2, 2, 0, 2]]
    assert int(not_taken) == 2
