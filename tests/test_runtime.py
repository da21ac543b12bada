"""AP runtime: program-graph scheduler over device-sharded array pools.

Acceptance contract (ISSUE 4):

- a ProgramGraph of >= 2 independent tiled MAC programs executed by the
  Runtime is bit-exact vs running each via run_mac_tiled sequentially, with
  exact APStats parity, and the modeled graph makespan is strictly below
  the sequential wall-cycle sum when the bank holds > 1 array;
- DevicePool output digits + APStats are bit-identical to single-array
  execute, including over real multi-device meshes (subprocess test under
  XLA_FLAGS=--xla_force_host_platform_device_count=4);
- scheduler property: results are independent of the (valid topological)
  execution order, and makespan <= sequential for random DAGs;
- the serve engine with ap_ctx runs a whole forward pass AP-backed and
  reports aggregated per-request cycles + Table XI energy.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import apc
from repro.core import ap


def _stats_equal(a: ap.APStats, b: ap.APStats) -> None:
    assert (a.sets, a.resets) == (b.sets, b.resets)
    assert (a.n_compare_cycles, a.n_write_cycles) == \
        (b.n_compare_cycles, b.n_write_cycles)
    assert np.array_equal(a.mismatch_hist, b.mismatch_hist)


def _mac_inputs(radix, K, max_abs, rows, seed):
    rng = np.random.default_rng(seed)
    x = rng.integers(-max_abs, max_abs + 1, (rows, K))
    w = rng.integers(-1, 2, (rows, K))
    return x, w


# ---------------------------------------------------------------------------
# Acceptance: independent tiled MACs through the runtime
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("radix", [3, 5])
def test_runtime_two_macs_bit_exact_vs_sequential(radix):
    """>= 2 independent tiled MACs as ONE graph: digits bit-exact vs
    sequential run_mac_tiled, exact APStats parity, and graph makespan
    strictly below the sequential wall-cycle sum (2 arrays > 1)."""
    K, max_abs = 7, 3
    width = apc.mac_acc_width(radix, K, max_abs)
    tiled = apc.compile_mac_tiled(radix, K, width, 3)
    cols = max(tiled.min_cols, 2 * width + 1)
    x1, w1 = _mac_inputs(radix, K, max_abs, 23, radix)
    x2, w2 = _mac_inputs(radix, K, max_abs, 31, radix + 100)

    st_seq = ap.APStats(radix=radix)
    pool_seq = apc.ArrayPool(n_arrays=2, rows=8, cols=cols)
    a1 = apc.run_mac_tiled(jnp.asarray(x1, jnp.int32),
                           jnp.asarray(w1, jnp.int8), tiled, pool=pool_seq,
                           stats=st_seq)
    a2 = apc.run_mac_tiled(jnp.asarray(x2, jnp.int32),
                           jnp.asarray(w2, jnp.int8), tiled, pool=pool_seq,
                           stats=st_seq)

    st_rt = ap.APStats(radix=radix)
    rt = apc.Runtime(apc.ArrayPool(n_arrays=2, rows=8, cols=cols))
    d1, d2 = rt.run_mac_graph(
        [(jnp.asarray(x1, jnp.int32), jnp.asarray(w1, jnp.int8), tiled),
         (jnp.asarray(x2, jnp.int32), jnp.asarray(w2, jnp.int8), tiled)],
        stats=st_rt)
    g1 = apc.mac.decode_signed_digits_jnp(d1, radix)
    g2 = apc.mac.decode_signed_digits_jnp(d2, radix)
    assert np.array_equal(np.asarray(g1), np.asarray(a1))
    assert np.array_equal(np.asarray(g2), np.asarray(a2))
    assert np.array_equal(np.asarray(g1), (x1 * w1).sum(axis=1))
    _stats_equal(st_seq, st_rt)
    rep = rt.last_report
    assert rep["makespan_cycles"] < rep["sequential_cycles"]
    # schedule-static totals match what the stats charged
    assert st_rt.n_write_cycles == 2 * tiled.n_write_cycles


def test_runtime_matmul_route_bit_exact():
    """ternary_matmul(impl='ap', runtime=) equals impl='ref' bit-for-bit."""
    from repro.kernels.ternary_matmul.ops import (quantize_and_pack,
                                                  ternary_matmul)
    from repro.kernels.ternary_matmul.ref import ternary_matmul_ref
    rng = np.random.default_rng(3)
    m, k, n, max_abs = 3, 24, 4, 3
    w = jnp.asarray(rng.normal(0, 0.05, (k, n)), jnp.float32)
    packed, scale = quantize_and_pack(w)
    x = jnp.asarray(rng.integers(-max_abs, max_abs + 1, (m, k)), jnp.float32)
    width = apc.mac_acc_width(3, packed.shape[0] * 16, max_abs)
    rt = apc.Runtime(apc.ArrayPool(
        n_arrays=2, rows=8, cols=apc.mac_layout(12, width)["n_cols"]))
    st = ap.APStats(radix=3)
    y = ternary_matmul(x, packed, scale, impl="ap", runtime=rt, stats=st)
    assert np.array_equal(np.asarray(y),
                          np.asarray(ternary_matmul_ref(x, packed, scale)))
    assert st.n_write_cycles > 0
    assert rt.last_report["makespan_cycles"] <= \
        rt.last_report["sequential_cycles"]


def test_core_mac_tiled_runtime_route():
    x, w = _mac_inputs(3, 6, 2, 19, 7)
    width = apc.mac_acc_width(3, 6, 2)
    rt = apc.Runtime(apc.ArrayPool(
        n_arrays=2, rows=8, cols=apc.mac_layout(2, width)["n_cols"]))
    got = ap.mac_tiled(jnp.asarray(x, jnp.int32), jnp.asarray(w, jnp.int8),
                       3, width, k_tile=2, runtime=rt)
    assert np.array_equal(np.asarray(got), (x * w).sum(axis=1))
    with pytest.raises(ValueError, match="runtime"):
        ap.mac_tiled(jnp.asarray(x, jnp.int32), jnp.asarray(w, jnp.int8),
                     3, width, k_tile=2, runtime=rt,
                     pool=apc.ArrayPool(n_arrays=1, rows=8, cols=64))


# ---------------------------------------------------------------------------
# DevicePool: bank spans the mesh, bit parity vs single-array execute
# ---------------------------------------------------------------------------

def _device_mesh():
    devs = np.array(jax.devices())
    return jax.sharding.Mesh(devs.reshape(len(devs), 1), ("data", "model"))


def test_device_pool_parity_vs_execute():
    """Whatever the local device count (1 under plain pytest, 4 under the
    CI runtime shard's forced XLA flags): same digits, same APStats."""
    r, w, rows = 3, 5, 173
    rng = np.random.default_rng(11)
    a = rng.integers(0, r ** w, rows)
    b = rng.integers(0, r ** w, rows)
    arr = jnp.asarray(ap.encode_operands(a, b, r, w))
    compiled = apc.compile_named("add", r, w)
    out_e, tr_e = apc.execute(arr, compiled, collect_stats=True)
    pool = apc.DevicePool(_device_mesh(), n_arrays=2, rows=16, cols=2 * w + 1)
    assert pool.total_arrays == 2 * jax.device_count()
    out_p, tr_p = pool.run(arr, compiled, collect_stats=True)
    assert np.array_equal(np.asarray(out_e), np.asarray(out_p))
    _stats_equal(apc.to_ap_stats(tr_e, compiled, rows, r),
                 apc.to_ap_stats(tr_p, compiled, rows, r))
    # wall model: blocks split over devices, then local arrays
    wall = pool.wall_cycles(rows, compiled.n_compare_cycles,
                            compiled.n_write_cycles)
    blocks = (rows + 15) // 16
    blocks_per_dev = (blocks + pool.n_devices - 1) // pool.n_devices
    waves = (blocks_per_dev + pool.n_arrays - 1) // pool.n_arrays
    assert wall["waves"] == waves


def test_device_pool_no_mesh_degrades_to_array_pool():
    r, w, rows = 3, 4, 37
    rng = np.random.default_rng(2)
    a = rng.integers(0, r ** w, rows)
    b = rng.integers(0, r ** w, rows)
    arr = jnp.asarray(ap.encode_operands(a, b, r, w))
    compiled = apc.compile_named("add", r, w)
    pool = apc.DevicePool(None, n_arrays=3, rows=8, cols=2 * w + 1)
    assert pool.n_devices == 1 and pool.total_arrays == 3
    out_p, _ = pool.run(arr, compiled)
    out_e, _ = apc.execute(arr, compiled)
    assert np.array_equal(np.asarray(out_e), np.asarray(out_p))


def test_device_pool_zero_rows_and_validation():
    compiled = apc.compile_named("add", 3, 4)
    pool = apc.DevicePool(_device_mesh(), n_arrays=1, rows=8, cols=9)
    out, tr = pool.run(jnp.zeros((0, 9), jnp.int8), compiled,
                       collect_stats=True)
    assert out.shape == (0, 9) and int(tr.sets) == 0
    with pytest.raises(ValueError, match="columns wide"):
        pool.run(jnp.zeros((4, 4), jnp.int8), compiled)
    wide = apc.compile_named("add", 3, 8)           # 17 cols > pool's 9
    with pytest.raises(ValueError, match="columns wide"):
        pool.validate(wide)


@pytest.mark.slow              # subprocess with its own jax init + compiles
def test_runtime_multidevice_subprocess():
    """Real 4-device DevicePool + Runtime vs the single-array oracle:
    bit-identical digits, exact APStats parity, makespan < sequential."""
    import os
    import subprocess
    import sys
    import textwrap
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(repo, "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh
        from repro import apc
        from repro.core import ap

        devs = np.array(jax.devices())
        assert len(devs) == 4
        mesh = Mesh(devs.reshape(2, 2, 1), ("pod", "data", "model"))
        r, w, rows = 3, 5, 533            # uneven tail across 4 shards
        rng = np.random.default_rng(5)
        a = rng.integers(0, r ** w, rows)
        b = rng.integers(0, r ** w, rows)
        arr = jnp.asarray(ap.encode_operands(a, b, r, w))
        compiled = apc.compile_named("add", r, w)
        out_e, tr_e = apc.execute(arr, compiled, collect_stats=True)
        pool = apc.DevicePool(mesh, n_arrays=2, rows=32, cols=2 * w + 1)
        assert pool.n_devices == 4 and pool.total_arrays == 8
        out_p, tr_p = pool.run(arr, compiled, collect_stats=True)
        assert np.array_equal(np.asarray(out_e), np.asarray(out_p))
        se = apc.to_ap_stats(tr_e, compiled, rows, r)
        sp = apc.to_ap_stats(tr_p, compiled, rows, r)
        assert (se.sets, se.resets) == (sp.sets, sp.resets), (se, sp)
        assert (se.n_compare_cycles, se.n_write_cycles) == \\
               (sp.n_compare_cycles, sp.n_write_cycles)
        assert np.array_equal(se.mismatch_hist, sp.mismatch_hist)

        # runtime over the device-spanning bank: two independent MACs
        radix, K, max_abs = 3, 6, 2
        width = apc.mac_acc_width(radix, K, max_abs)
        cols = apc.mac_layout(2, width)["n_cols"]
        dpool = apc.DevicePool(mesh, n_arrays=2, rows=16, cols=cols)
        tiled = apc.compile_mac_tiled(radix, K, width, 2, max_cols=cols)
        rng = np.random.default_rng(6)
        macs, want = [], []
        for i in range(2):
            x = rng.integers(-max_abs, max_abs + 1, (70 + i, K))
            wt = rng.integers(-1, 2, (70 + i, K))
            macs.append((jnp.asarray(x, jnp.int32),
                         jnp.asarray(wt, jnp.int8), tiled))
            want.append((x * wt).sum(axis=1))
        st = ap.APStats(radix=radix)
        rt = apc.Runtime(dpool)
        digs = rt.run_mac_graph(macs, stats=st)
        for d, wnt in zip(digs, want):
            got = apc.mac.decode_signed_digits_jnp(d, radix)
            assert np.array_equal(np.asarray(got), wnt)
        rep = rt.last_report
        assert rep["n_arrays_total"] == 8
        assert rep["makespan_cycles"] < rep["sequential_cycles"]
        assert st.n_write_cycles == 2 * tiled.n_write_cycles
        print("OK")
    """)], capture_output=True, text=True, env=env, timeout=540)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout


# ---------------------------------------------------------------------------
# Scheduler properties: order independence + makespan bound on random DAGs
# ---------------------------------------------------------------------------

def _random_dag(seed, rows=21, width=4, radix=3):
    """Random DAG of `add` programs: roots hold random operand rows; a
    child adds its two dependencies' result digit blocks (A + B -> B)."""
    rng = np.random.default_rng(seed)
    compiled = apc.compile_named("add", radix, width)
    graph = apc.ProgramGraph()
    n_nodes = int(rng.integers(4, 11))
    for i in range(n_nodes):
        n_deps = 0 if i < 2 else int(rng.integers(0, min(i, 2) + 1))
        if n_deps == 0:
            a = rng.integers(0, radix, (rows, 2 * width + 1)).astype(np.int8)
            a[:, -1] = 0                                     # clear carry

            def build(_a=a):
                return jnp.asarray(_a)

            graph.add(compiled, rows=rows, build=build,
                      result_cols=(width, 2 * width), label=f"root{i}")
        else:
            deps = tuple(int(d) for d in
                         rng.choice(i, size=n_deps, replace=False))
            if n_deps == 1:
                deps = deps * 2                              # self-add

            def build(*parts):
                return jnp.concatenate(
                    [parts[0], parts[1],
                     jnp.zeros((parts[0].shape[0], 1), jnp.int8)], axis=1)

            graph.add(compiled, rows=rows, build=build, deps=deps[:2],
                      result_cols=(width, 2 * width), label=f"n{i}")
    return graph, rng


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4, 5])
def test_runtime_random_dag_order_independence(seed):
    """Any valid topological execution order yields identical digits and
    identical accumulated APStats; makespan <= sequential always."""
    graph, rng = _random_dag(seed)
    pool = apc.ArrayPool(n_arrays=int(rng.integers(1, 4)),
                         rows=int(rng.integers(6, 30)), cols=9)
    rt = apc.Runtime(pool)
    st_a, st_b = ap.APStats(radix=3), ap.APStats(radix=3)
    res_a = rt.run_graph(graph, stats=st_a)
    # a different valid topo order: reverse wavefronts internally
    order = [nid for wave in graph.wavefronts() for nid in reversed(wave)]
    res_b = rt.run_graph(graph, stats=st_b, order=order)
    for nid in range(len(graph)):
        assert np.array_equal(np.asarray(res_a[nid]), np.asarray(res_b[nid]))
    _stats_equal(st_a, st_b)
    rep = res_a.report
    assert rep["makespan_cycles"] <= rep["sequential_cycles"]
    assert rep["n_nodes"] == len(graph)
    # invalid orders are rejected
    if any(n.deps for n in graph.nodes):
        first_dep = next(i for i, n in enumerate(graph.nodes) if n.deps)
        bad = [first_dep] + [i for i in range(len(graph)) if i != first_dep]
        with pytest.raises(ValueError, match="dependencies"):
            rt.run_graph(graph, order=bad)
    with pytest.raises(ValueError, match="permutation"):
        rt.run_graph(graph, order=[0] * len(graph))


def test_graph_validation_and_wavefronts():
    compiled = apc.compile_named("add", 3, 3)
    g = apc.ProgramGraph()
    a = g.add(compiled, rows=4, build=lambda: jnp.zeros((4, 7), jnp.int8))
    with pytest.raises(ValueError, match="topological"):
        g.add(compiled, rows=4, build=lambda r: r, deps=(5,))
    b = g.add(compiled, rows=4,
              build=lambda r: jnp.concatenate(
                  [r, r, jnp.zeros((4, 1), jnp.int8)], axis=1),
              deps=(a,), result_cols=(3, 6))
    assert g.wavefronts() == [[a], [b]]
    assert g.sinks() == [b]
    tot = g.total_cycles()
    assert tot["write_cycles"] == 2 * compiled.n_write_cycles
    # rows mismatch between declared and built arrays is caught
    g2 = apc.ProgramGraph()
    g2.add(compiled, rows=9, build=lambda: jnp.zeros((4, 7), jnp.int8))
    with pytest.raises(ValueError, match="declared rows"):
        apc.Runtime(apc.ArrayPool(n_arrays=1, rows=8, cols=7)).run_graph(g2)


def test_graph_makespan_model():
    """Hand-checked occupancy: two independent 1-block nodes on 2 arrays
    run in one wave; a dependent node starts after both."""
    compiled = apc.compile_named("add", 3, 3)
    cyc = compiled.n_compare_cycles + compiled.n_write_cycles
    g = apc.ProgramGraph()
    mk = lambda: jnp.zeros((4, 7), jnp.int8)
    a = g.add(compiled, rows=4, build=mk)
    b = g.add(compiled, rows=4, build=mk)
    c = g.add(compiled, rows=4,
              build=lambda r, s: jnp.concatenate(
                  [r, s, jnp.zeros((4, 1), jnp.int8)], axis=1),
              deps=(a, b), result_cols=(3, 6))
    rep = apc.graph_makespan(g, n_arrays=2, rows_per_array=8)
    assert rep["makespan_cycles"] == 2 * cyc          # (a||b) then c
    assert rep["sequential_cycles"] == 3 * cyc
    rep1 = apc.graph_makespan(g, n_arrays=1, rows_per_array=8)
    assert rep1["makespan_cycles"] == rep1["sequential_cycles"] == 3 * cyc
    with pytest.raises(ValueError, match="geometry"):
        apc.graph_makespan(g, n_arrays=0, rows_per_array=8)


def test_mac_fold_plan_matches_reduce_groups():
    """The shared fold plan is the single source of the reduction-chain
    cycle accounting: stages mirror (reduce_groups, reduce_programs) and
    consume every partial exactly once."""
    tiled = apc.compile_mac_tiled(3, 9, 3, 1, max_cols=3 * 3 + 1)
    plan = apc.mac_fold_plan(tiled)
    assert len(plan) == len(tiled.reduce_groups) > 1
    consumed = [p for st in plan for p in st.parts if p != apc.CARRIED]
    assert sorted(consumed) == list(range(len(tiled.tiles)))
    assert all(st.parts[0] == apc.CARRIED for st in plan[1:])
    for st, g in zip(plan, tiled.reduce_groups):
        assert len(st.parts) == g
        assert (st.out_lo, st.out_hi) == ((g - 1) * 3, g * 3)
    # untiled MAC: no stages
    assert apc.mac_fold_plan(apc.compile_mac_tiled(3, 4, 3, 4)) == ()


# ---------------------------------------------------------------------------
# AP-backed layers + serve engine
# ---------------------------------------------------------------------------

def _tiny_ctx(n_arrays=4, rows=64, cols=96, x_levels=7):
    pool = apc.ArrayPool(n_arrays=n_arrays, rows=rows, cols=cols)
    return apc.APServeContext(apc.Runtime(pool), x_levels=x_levels)


def test_ap_linear_exact_on_integer_grid():
    """Integer activations on the quantization grid pass through exactly:
    APLinear == (x @ w_ter) * w_scale bit-for-bit."""
    from repro.kernels.ternary_matmul.ops import quantize_and_pack
    rng = np.random.default_rng(8)
    k, n, t = 16, 5, 6
    w = jnp.asarray(rng.normal(0, 0.05, (k, n)), jnp.float32)
    packed, scale = quantize_and_pack(w)
    ctx = _tiny_ctx()
    lin = apc.APLinear.from_packed(packed, scale)
    x = rng.integers(-7, 8, (t, k)).astype(np.float32)
    x[0, 0] = 7.0                          # pin the grid scale to exactly 1
    y = lin(jnp.asarray(x), ctx)
    from repro.kernels.ternary_matmul.ref import unpack_ternary
    w_ter = np.asarray(unpack_ternary(packed, dtype=jnp.int8))[:k]
    want = (x.astype(np.int64) @ w_ter.astype(np.int64)).astype(np.float32) \
        * np.asarray(scale)[None, :]
    assert np.array_equal(np.asarray(y), want)
    assert ctx.stats.n_write_cycles > 0
    rep = ctx.report()
    assert rep["energy_total_j"] > 0
    assert rep["makespan_cycles"] <= rep["sequential_cycles"]


def test_mlp_ap_runs_and_aggregates():
    from repro.models import mlp as mlp_mod
    from repro.models.quant import pack_mlp_params
    rng = np.random.default_rng(9)
    d, ff, t = 12, 16, 3
    p = {"w1": jnp.asarray(rng.normal(0, .2, (d, ff)), jnp.float32),
         "w3": jnp.asarray(rng.normal(0, .2, (d, ff)), jnp.float32),
         "w2": jnp.asarray(rng.normal(0, .2, (ff, d)), jnp.float32)}
    packed = pack_mlp_params(p)
    x = jnp.asarray(rng.normal(0, 1, (1, t, d)), jnp.float32)
    ctx = _tiny_ctx(cols=64)
    with apc.ap_serving(ctx):
        y = mlp_mod.mlp(packed, x)
    assert y.shape == (1, t, d)
    assert np.isfinite(np.asarray(y)).all()
    rep = ctx.report()
    # gate+up ran as one 2-projection graph, down as a second
    assert ctx.n_graphs == 2
    assert rep["write_cycles"] > 0
    assert rep["makespan_cycles"] < rep["sequential_cycles"]
    # without the context, the packed float path is untouched
    y_f = mlp_mod.mlp(packed, x)
    assert y_f.shape == y.shape


def test_moe_ap_dispatch_runs_and_combines():
    from repro.configs.base import MoECfg
    from repro.models import moe as moe_mod
    cfg = MoECfg(n_experts=3, top_k=2, d_ff=8)
    p = moe_mod.init_moe(jax.random.PRNGKey(0), 8, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 3, 8), jnp.float32)
    ctx = _tiny_ctx(cols=48)
    with apc.ap_serving(ctx):
        y = moe_mod.moe_ffn(p, x, cfg, "silu", mesh=None)
    assert y.shape == x.shape
    assert np.isfinite(np.asarray(y)).all()
    assert ctx.n_graphs == 2               # experts' w1+w3, then w2
    assert ctx.report()["makespan_cycles"] <= \
        ctx.report()["sequential_cycles"]


def test_moe_ap_dispatch_empty_tokens_runs_no_graphs():
    """ISSUE 7 satellite: T == 0 short-circuits — zero graphs, not two
    empty ones, and no w2_lins[0] indexing before the guards."""
    from repro.apc.layers import APLinear, ap_moe_dispatch
    rng = np.random.default_rng(2)
    ctx = _tiny_ctx(cols=96)
    w1 = [APLinear.from_dense(rng.normal(0, .2, (8, 6)))]
    w3 = [APLinear.from_dense(rng.normal(0, .2, (8, 6)))]
    w2 = [APLinear.from_dense(rng.normal(0, .2, (6, 8)))]
    out = ap_moe_dispatch(ctx, jnp.zeros((0, 8), jnp.float32),
                          jnp.zeros((0, 2), jnp.int32),
                          jnp.zeros((0, 2), jnp.float32), w1, w3, w2,
                          jax.nn.silu)
    assert out.shape == (0, 8)
    assert ctx.n_graphs == 0
    # top-k == 0 with tokens present: all-zero output, still no graphs
    out = ap_moe_dispatch(ctx, jnp.ones((3, 8), jnp.float32),
                          jnp.zeros((3, 0), jnp.int32),
                          jnp.zeros((3, 0), jnp.float32), w1, w3, w2,
                          jax.nn.silu)
    assert out.shape == (3, 8) and not np.any(np.asarray(out))
    assert ctx.n_graphs == 0


def test_moe_ap_dispatch_empty_expert_lists_raise():
    from repro.apc.layers import APLinear, ap_moe_dispatch
    ctx = _tiny_ctx(cols=96)
    x = jnp.ones((2, 8), jnp.float32)
    ids = jnp.zeros((2, 1), jnp.int32)
    gates = jnp.ones((2, 1), jnp.float32)
    with pytest.raises(ValueError, match="at least one expert"):
        ap_moe_dispatch(ctx, x, ids, gates, [], [], [], jax.nn.silu)
    lin = APLinear.from_dense(np.random.default_rng(0).normal(size=(8, 6)))
    with pytest.raises(ValueError, match="lengths disagree"):
        ap_moe_dispatch(ctx, x, ids, gates, [lin], [lin, lin], [lin],
                        jax.nn.silu)


def test_moe_ap_dispatch_single_expert_routing():
    """All tokens routed to one expert of several: graphs only carry the
    populated expert and the combine matches the dense reference."""
    from repro.apc.layers import APLinear, ap_moe_dispatch
    rng = np.random.default_rng(5)
    ctx = _tiny_ctx(cols=96)
    E, d, ff, t = 3, 8, 6, 4
    w1s = [rng.normal(0, .2, (d, ff)) for _ in range(E)]
    w3s = [rng.normal(0, .2, (d, ff)) for _ in range(E)]
    w2s = [rng.normal(0, .2, (ff, d)) for _ in range(E)]
    w1 = [APLinear.from_dense(w) for w in w1s]
    w3 = [APLinear.from_dense(w) for w in w3s]
    w2 = [APLinear.from_dense(w) for w in w2s]
    x = jnp.asarray(rng.normal(0, 1, (t, d)), jnp.float32)
    ids = jnp.full((t, 1), 1, jnp.int32)        # everyone -> expert 1
    gates = jnp.ones((t, 1), jnp.float32)
    out = ap_moe_dispatch(ctx, x, ids, gates, w1, w3, w2, jax.nn.silu)
    assert out.shape == (t, d)
    assert np.isfinite(np.asarray(out)).all()
    assert ctx.n_graphs == 2                    # one gate+up, one down
    # the two graphs carry ONLY expert 1's projections (2 MACs, then 1)
    assert ctx.n_programs > 0


@pytest.mark.slow          # a full (tiny) engine request through the AP path
def test_engine_ap_backed_request_report():
    from repro.configs import get_smoke_config
    from repro.launch.mesh import make_smoke_mesh
    from repro.models import model as M
    from repro.models.quant import quantize_model_params
    from repro.serve.engine import Engine, ServeCfg
    base = get_smoke_config("qwen3-0.6b")
    cfg = base.with_(n_layers=1, d_model=16, d_ff=24, n_heads=2,
                     n_kv_heads=2, head_dim=8, vocab=32,
                     ternary=base.ternary.__class__(enabled=True))
    mesh = make_smoke_mesh()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    qparams = quantize_model_params(params)
    ctx = _tiny_ctx(cols=64)
    eng = Engine(cfg, qparams, mesh, ServeCfg(max_len=8), ap_ctx=ctx)
    toks = eng.generate(np.array([[3]], dtype=np.int32), 1)
    assert toks.shape == (1, 1)
    rep = eng.ap_report()
    assert rep["write_cycles"] > 0 and rep["n_graphs"] >= 2
    assert rep["energy_total_j"] > 0
    assert rep["makespan_cycles"] <= rep["sequential_cycles"]
    # second request re-aggregates from zero
    first = rep["write_cycles"]
    eng.generate(np.array([[5]], dtype=np.int32), 1)
    assert eng.ap_report()["write_cycles"] == first
