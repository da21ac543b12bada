"""Dense MLP projections served as stacks of one projection.

Each dense projection is converted once, at ``Engine`` construction, into
an :class:`~repro.apc.layers.APExpertStack` of one projection, keyed by
layer and projection, and added to a graph as a grouped MAC whose rows all
read projection 0: the first tile node's build makes every tile's rows in
one jitted call.

Contract: digits, ``APStats`` (histogram included), makespan and tokens
are bit-identical to serving each projection as an
:class:`~repro.apc.layers.APLinear` built per step (the path the stacks
replaced, kept here as :func:`_aplinear_mlp_ap`); a served request
derives no weights and builds no tile row eagerly.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import apc
from repro.apc.layers import APExpertStack, APLinear, APSink
from repro.core.ap import APStats
from repro.models import mlp as mlp_mod
from repro.models.quant import MLP_KEYS


def _ctx(cols=64, rows=16):
    pool = apc.ArrayPool(n_arrays=4, rows=rows, cols=cols)
    return apc.APServeContext(apc.Runtime(pool), x_levels=7)


def _engine(n_layers=1):
    from repro.configs import get_smoke_config
    from repro.launch.mesh import make_smoke_mesh
    from repro.models import model as M
    from repro.models.quant import quantize_model_params
    from repro.serve.engine import Engine, ServeCfg
    base = get_smoke_config("qwen3-0.6b")
    cfg = base.with_(n_layers=n_layers, d_model=16, d_ff=24, n_heads=2,
                     n_kv_heads=2, head_dim=8, vocab=32,
                     ternary=base.ternary.__class__(enabled=True))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return Engine(cfg, quantize_model_params(params), make_smoke_mesh(),
                  ServeCfg(max_len=8), ap_ctx=_ctx())


def _aplinear_mlp_ap(p, x, act, ctx):
    """The dense MLP with each projection an APLinear made from the
    packed weights in every step, its tiles built one by one."""
    from repro.apc.graph import ProgramGraph
    from repro.models.common import act_fn
    store = ctx.runtime.pool.resident
    lin1, lin3, lin2 = (
        APLinear.from_packed(p[f"{n}_packed"], p[f"{n}_scale"],
                             radix=ctx.radix, label=f"mlp.{n}", store=store)
        for n in MLP_KEYS)
    lead, d = x.shape[:-1], x.shape[-1]
    x_int, s_x = ctx.quantize(x.reshape(-1, d))
    g1 = ProgramGraph()
    c1 = lin1.add_call(g1, x_int, max_cols=ctx.max_cols, max_q=ctx.x_levels)
    c3 = lin3.add_call(g1, x_int, max_cols=ctx.max_cols, max_q=ctx.x_levels)
    res1 = ctx.run_graph(g1)
    h = act_fn(act)(c1.decode(res1, s_x)) * c3.decode(res1, s_x)
    h_int, s_h = ctx.quantize(h)
    g2 = ProgramGraph()
    c2 = lin2.add_call(g2, h_int, max_cols=ctx.max_cols, max_q=ctx.x_levels)
    y = c2.decode(ctx.run_graph(g2), s_h)
    return y.reshape(*lead, y.shape[-1]).astype(x.dtype)


def _stats(st: APStats) -> dict:
    return {k: (v.tolist() if isinstance(v, np.ndarray) else v)
            for k, v in dataclasses.asdict(st).items()}


def _counts(names=("mac.tile_row_builds", "mac.tile_rows_eager",
                   "mac.weight_encodes")) -> dict:
    return apc.get_registry().counter_values(names)


# ---------------------------------------------------------------------------
# One projection: tile rows, digits, APStats, makespan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t", [1, 2])
@pytest.mark.parametrize("k,n", [(48, 80), (80, 48)], ids=["gate_up", "down"])
def test_projection_bit_identical_to_aplinear(k, n, t):
    """The chat cell's two projection shapes (d_model x d_ff and back),
    scaled down: a stack of one projection builds the same rows for every
    tile as APLinear's per-tile builds, and runs to the same accumulator
    digits, APStats, makespan and decoded values."""
    rng = np.random.default_rng(100 * k + t)
    w = jnp.asarray(rng.integers(-1, 2, (k, n)), jnp.int8)
    scale = jnp.asarray(rng.uniform(0.5, 1.5, n), jnp.float32)
    x_int = jnp.asarray(rng.integers(-7, 8, (t, k)), jnp.int32)
    ctx = _ctx()
    store = ctx.runtime.pool.resident
    lin = APLinear(w, scale, label="lin", store=store)
    stack = APExpertStack(w[None], scale[None], label="stack", store=store)
    g_lin, g_st = apc.ProgramGraph(), apc.ProgramGraph()
    c_lin = lin.add_call(g_lin, x_int, max_cols=ctx.max_cols, max_q=7)
    c_st = stack.add_call(g_st, x_int, np.zeros(t, np.int32),
                          max_cols=ctx.max_cols, max_q=7)
    assert g_lin.meta == g_st.meta
    tiles = [i for i, nd in enumerate(g_lin.nodes) if not nd.deps]
    assert len(tiles) > 2 and len(g_lin) == len(g_st)
    before = _counts()
    for a, b in zip(g_lin.nodes, g_st.nodes):
        assert a.compiled is b.compiled
        assert (a.rows, a.deps, a.result_cols, a.upload_cycles) == \
            (b.rows, b.deps, b.result_cols, b.upload_cycles)
    for i in tiles:
        assert np.array_equal(np.asarray(g_lin.nodes[i].build()),
                              np.asarray(g_st.nodes[i].build()))
    count = {name: v - before[name] for name, v in _counts().items()}
    assert count == {"mac.tile_row_builds": 1,
                     "mac.tile_rows_eager": len(tiles),
                     "mac.weight_encodes": 0}
    st_lin, st_st = APStats(radix=3), APStats(radix=3)
    res_lin = ctx.runtime.run_graph(g_lin, stats=st_lin)
    res_st = ctx.runtime.run_graph(g_st, stats=st_st)
    assert np.array_equal(np.asarray(res_lin[c_lin.node]),
                          np.asarray(res_st[c_st.node]))
    assert _stats(st_lin) == _stats(st_st)
    assert res_lin.report == res_st.report
    s_x = jnp.float32(0.37)
    y_lin = np.asarray(c_lin.decode(res_lin, s_x))
    assert np.array_equal(y_lin, np.asarray(c_st.decode(res_st, s_x)))
    want = (np.asarray(x_int, np.int64) @ np.asarray(w, np.int64)).astype(
        np.float32) * np.float32(0.37) * np.asarray(scale)[None, :]
    assert np.array_equal(y_lin, want)


def test_from_packed_serves_the_packed_digits():
    """``APExpertStack.from_packed`` holds the served digits and scale:
    one projection, K padded to whole 16s as packed."""
    from repro.kernels.ternary_matmul.ops import quantize_and_pack
    from repro.kernels.ternary_matmul.ref import unpack_ternary
    w = np.random.default_rng(3).normal(0, 0.05, (24, 5)).astype(np.float32)
    packed, scale = quantize_and_pack(jnp.asarray(w))
    stack = APExpertStack.from_packed(packed, scale)
    assert (stack.e, stack.k, stack.n) == (1, 32, 5)
    ter = np.asarray(unpack_ternary(packed, dtype=jnp.int8))
    assert np.array_equal(np.asarray(stack.plane), ter.T + 1)
    assert np.array_equal(np.asarray(stack.w_scale[0]), np.asarray(scale))


# ---------------------------------------------------------------------------
# Served through Engine and BatchServer
# ---------------------------------------------------------------------------

PROMPTS = [np.array([[3, 5]], np.int32), np.array([[7, 2]], np.int32)]


def test_engine_tokens_and_stats_match_aplinear_path(monkeypatch):
    """A tiny dense model served through ``Engine``: tokens, APStats
    (histogram included) and the request's accounting equal the APLinear
    path's, request by request."""
    def serve():
        eng = _engine()
        out = []
        for p in PROMPTS:
            toks = eng.generate(p, 2)
            rep = eng.ap_ctx.report()
            out.append((toks, _stats(eng.ap_ctx.stats), rep))
        return out

    got = serve()
    monkeypatch.setattr(mlp_mod, "mlp_ap", _aplinear_mlp_ap)
    want = serve()
    for (tg, sg, rg), (tw, sw, rw) in zip(got, want):
        assert np.array_equal(tg, tw)
        assert sg == sw
        assert rg == rw


def test_merged_wave_tokens_and_stats_match_aplinear_path(monkeypatch):
    """Two requests in merged ``BatchServer`` waves: each request's tokens,
    APStats and accounting equal the APLinear path's."""
    from repro.serve.batcher import AdmissionCfg, BatchServer
    report = APSink.report

    def report_with_hist(self, *a, **kw):
        rep = report(self, *a, **kw)
        rep["mismatch_hist"] = self.stats.mismatch_hist.tolist()
        return rep

    monkeypatch.setattr(APSink, "report", report_with_hist)

    def serve():
        eng = _engine()
        with BatchServer(eng, admission=AdmissionCfg(max_inflight=2)) as srv:
            hs = [srv.submit(p, 2) for p in PROMPTS]
            out = [(h.result(timeout=300), h.ap_report()) for h in hs]
        assert srv.n_waves < 2 * 3             # a wave merged the two
        return out

    got = serve()
    monkeypatch.setattr(mlp_mod, "mlp_ap", _aplinear_mlp_ap)
    want = serve()
    for (tg, rg), (tw, rw) in zip(got, want):
        assert np.array_equal(tg, tw)
        assert rg == rw


def test_served_request_derives_no_weights(monkeypatch):
    """After ``Engine`` construction and one warm request, a request makes
    every tile's rows in one call per projection (3 a layer-step), builds
    no tile eagerly, encodes no weight and makes no APLinear; so does each
    request of a merged wave."""
    from repro.serve.batcher import AdmissionCfg, BatchServer
    n_layers = 2
    eng = _engine(n_layers)
    ctx = eng.ap_ctx
    keys = {mlp_mod.mlp_key(i, n) for i in range(n_layers)
            for n in MLP_KEYS}
    assert set(ctx._stacks) == keys
    eng.generate(PROMPTS[0], 1)                       # warm

    def no_aplinear(*a, **kw):
        raise AssertionError("a served step made an APLinear")

    monkeypatch.setattr(APLinear, "__init__", no_aplinear)
    before = _counts()
    eng.generate(PROMPTS[1], 2)                       # 3 model steps
    count = {name: v - before[name] for name, v in _counts().items()}
    assert count == {"mac.tile_row_builds": 3 * n_layers * 3,
                     "mac.tile_rows_eager": 0, "mac.weight_encodes": 0}
    with BatchServer(eng, admission=AdmissionCfg(max_inflight=2)) as srv:
        before = _counts()
        hs = [srv.submit(p, 2) for p in PROMPTS]
        for h in hs:
            h.result(timeout=300)
    count = {name: v - before[name] for name, v in _counts().items()}
    assert count == {"mac.tile_row_builds": 3 * n_layers * 3 * len(PROMPTS),
                     "mac.tile_rows_eager": 0, "mac.weight_encodes": 0}
    assert set(ctx._stacks) == keys
