"""Sparse-expert serving on the AP bank at a small size: one chip's share of
the experts, one grouped MAC per expert projection, the layer stack run layer
by layer, against the plain reference of ``bench/reference/qwen3_moe.py``
and the check of ``bench/drivers/serve_moe.py``."""
import sys
import threading
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import apc
from repro.apc.metrics import get_registry
from repro.configs.base import MoECfg
from repro.models import model as M

BENCH = Path(__file__).resolve().parent.parent / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))
import run  # noqa: E402
from reference import qwen3, qwen3_moe  # noqa: E402

SEED = 11
# 3 layers (more than the 2 that ran unrolled before: the stack would have
# run under lax.scan, in float), 8 experts, top-4, this chip holds 4
TINY = {"hidden_size": 32, "moe_intermediate_size": 16,
        "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 16,
        "vocab_size": 64, "num_hidden_layers": 3, "router_width": 8,
        "num_experts_per_tok": 4, "held_experts": [0, 4],
        "assumed": {"bank": {"n_arrays": 2, "rows": 64, "cols": 96},
                    "x_levels": 7, "capacity_factor": 1.25}}
PROMPTS = ([5, 9, 3], [7, 2])
N_NEW = (3, 5)
# served in float32 here (the KV cache stays bfloat16: the program reads
# 0.005), so that the gap reads faults of a few per cent in the MoE layers
GAP = 0.01


def tiny_config(**kw) -> dict:
    config = run.load_json(BENCH / "configs" / "qwen3-30b-a3b-l4.json")
    return {**config, **TINY, "torch_dtype": "float32",
            "serving": dict(config["serving"], compute_dtype="float32"),
            "check": dict(config["check"], logit_rel_gap=GAP), **kw}


def counters() -> dict:
    return {k: v for k, v in get_registry().snapshot().items()
            if isinstance(v, int)}


def delta(before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in counters().items()}


@pytest.fixture(scope="module")
def driver():
    return run.load_module(BENCH / "drivers" / "serve_moe.py")


@pytest.fixture(scope="module")
def served(driver):
    """One small served session, warmed as the driver's set-up warms it:
    two requests stepped together (merged waves, then lone ones) through
    ``BatchServer``, every step recorded by the driver; also the names of
    the programs they compiled."""
    config = tiny_config()
    traffic = run.load_json(BENCH / "traffic" / "chat-c2.json")
    state = driver.build(config, traffic, SEED)
    driver.warm_step(state)
    driver.warm_calls(state)
    driver.serve.warm(state, 2)
    compiles = run.CompileCount()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    state["requests"] = []
    before = counters()
    hs = [state["srv"].submit(np.asarray([p], np.int32), n)
          for p, n in zip(PROMPTS, N_NEW)]
    for h in hs:
        h.result(timeout=600)
    steps = driver.served_steps(state)
    yield state, steps, delta(before), list(compiles.names)
    state["srv"].close()


def serve_one(driver, state, prompt=(4, 6, 1, 2)) -> tuple[list, dict]:
    """One more request through the session's server: its served steps
    and the counters over it."""
    n = len(state["requests"])
    before = counters()
    state["srv"].submit(np.asarray([prompt], np.int32), 2).result(
        timeout=600)
    return (driver.served_steps(dict(state, requests=state["requests"][n:])),
            delta(before))


def failing(checks) -> set:
    return {c["name"] for c in checks if c["value"] > c["limit"]}


def test_engine_batchserver_against_reference(driver, served):
    """Prefill and decode through Engine/BatchServer under an AP context:
    every step's logits within the limit of the reference's full forward
    pass, every served token greedy, every layer of every step on the
    bank."""
    state, steps, count, _ = served
    assert M._layer_plan(state["cfg"])[0] > 2       # once a lax.scan
    checks = driver.compare(state["config"], SEED, steps, count)
    assert not failing(checks), checks
    n_steps = sum(len(r["tokens"]) for r in steps)
    assert n_steps == sum(len(p) + n - 1 for p, n in zip(PROMPTS, N_NEW))
    assert count["moe.layer_calls"] + count.get("moe.layers_empty", 0) \
        == 3 * n_steps
    assert count["moe.layer_calls"] > 0
    assert all(r["off_bank"] == 0 for r in steps)
    assert count["moe.pairs_routed"] == 3 * n_steps * 4


def test_warm_calls_leave_no_shape_to_compile(served):
    """After the driver's warm-up (a model step, one layer call of every
    lone and merged shape, the server's warm waves), the session's merged
    and lone waves, routed as they fall, compile nothing."""
    state, steps, count, compiles = served
    assert count["moe.layer_calls"] >= 8
    assert compiles == []


def _drop_a_held_expert(real):
    """Each call leaves out the lowest held expert it routes to."""
    def dispatch(ctx, x2d, ids, gates, w1, *a, first=0, **kw):
        e = np.asarray(ids).reshape(-1)
        held = e[(e >= first) & (e < first + w1.e)]
        if held.size:
            gates = jnp.where(ids == held.min(), 0.0, gates)
        return real(ctx, x2d, ids, gates, w1, *a, first=first, **kw)
    return dispatch


def _unnormalised_route(x2d, router, cfg):
    logits = x2d.astype(jnp.float32) @ router.astype(jnp.float32)
    gates, experts = jax.lax.top_k(jax.nn.softmax(logits, axis=-1),
                                   cfg.top_k)
    return gates, experts.astype(jnp.int32)


def _float_layer_1(real):
    """Layer 1's FFN computed off the bank: the reference's own MoE."""
    config = tiny_config()
    m = qwen3_moe.dims(config)
    w = qwen3_moe.weights(config, SEED)["layers"][1]

    def ffn(p, x, cfg, act, ctx):
        if apc.current_ap_layer() != 1:
            return real(p, x, cfg, act, ctx)
        u = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
        none = (jnp.asarray(g[1]) for g in qwen3_moe.not_given(config,
                                                                u.shape[0]))
        y, _, _ = qwen3_moe.moe(w, u, m, 7, jnp.matmul, *none, 0.0, 0.0)
        return y.reshape(x.shape).astype(x.dtype)
    return ffn


FAULTS = {
    "expert_output_altered": (
        "repro.apc.layers.ap_moe_dispatch",
        lambda real: lambda *a, **kw: 1.5 * real(*a, **kw),
        "logit_rel_gap"),
    "held_expert_dropped": ("repro.apc.layers.ap_moe_dispatch",
                            _drop_a_held_expert, "logit_rel_gap"),
    "gates_not_renormalised": ("repro.models.moe._route",
                               lambda real: _unnormalised_route,
                               "logit_rel_gap"),
    "layer_in_float": ("repro.models.moe.moe_ffn_ap", _float_layer_1,
                       "layer_steps_off_bank"),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_fails_the_check(driver, served, monkeypatch, fault):
    state = served[0]
    target, make, must_fail = FAULTS[fault]
    mod, name = target.rsplit(".", 1)
    real = getattr(sys.modules[mod], name)
    monkeypatch.setattr(sys.modules[mod], name, make(real))
    steps, count = serve_one(driver, state)
    monkeypatch.undo()
    checks = driver.compare(state["config"], SEED, steps, count)
    assert must_fail in failing(checks), checks


def test_route_tie_taken_and_route_off_fails(driver, served):
    """A given route that differs from the reference's own is taken where
    the k-th and (k+1)-th probabilities lie within the margin (here: any,
    at a margin of 1), and counted off outside it (margin 0), which fails
    the check."""
    state, steps, count, _ = served
    config = state["config"]
    r = steps[0]
    given = tuple(g.copy() for g in r["given"])
    route = given[2][0, 0]
    route[0] = next(e for e in range(8) if e not in route)
    w = qwen3_moe.weights(config, SEED)
    args = (w, config, r["tokens"], 7, 16)
    _, _, counts = qwen3_moe.prefix_logits(*args, given=given,
                                           tie=1 / 32, route_tie=1.0)
    assert counts["routes_taken"] >= 1 and counts["routes_off"] == 0
    _, _, counts = qwen3_moe.prefix_logits(*args, given=given,
                                           tie=1 / 32, route_tie=0.0)
    assert counts["routes_off"] >= 1
    off = [dict(r, given=given)]
    cfg0 = dict(config, check=dict(config["check"], route_tie=0.0))
    assert "routes_off" in failing(driver.compare(cfg0, SEED, off, count))
    cfg1 = dict(config, check=dict(config["check"], route_tie=1.0))
    assert "routes_off" not in failing(driver.compare(cfg1, SEED, off,
                                                      count))


def _tiny_ctx(cols=96):
    pool = apc.ArrayPool(n_arrays=2, rows=64, cols=cols)
    return apc.APServeContext(apc.Runtime(pool), x_levels=7)


def test_four_shares_sum_to_the_uncut_layer():
    """One layer, 8 experts over 4 chips: the 4 shares' AP outputs sum to
    the reference's layer over all 8 experts.  Both take one given route
    (which puts 2 pairs on every share), gated by the router."""
    config = tiny_config(held_experts=[0, 8])
    m = qwen3_moe.dims(config)
    p = qwen3_moe.weights(config, SEED)["layers"][0]
    u = jax.random.normal(jax.random.PRNGKey(3), (2, m["d"]), jnp.float32)
    ids = jnp.asarray([[0, 2, 4, 6], [7, 5, 3, 1]], jnp.int32)
    probs = jax.nn.softmax(u @ p["router"], axis=-1)
    gates = jnp.take_along_axis(probs, ids, axis=1)
    gates = gates / gates.sum(-1, keepdims=True)
    given = (jnp.full((2, m["d"]), qwen3.NOT_GIVEN, jnp.int32),
             jnp.full((2, 8, m["ff"]), qwen3.NOT_GIVEN, jnp.int32), ids)
    mm = lambda a, b: jnp.matmul(a, b, precision=qwen3.HIGHEST)  # noqa
    want, _, _ = qwen3_moe.moe(p, u, m, 7, mm, *given, 0.0, 1.0)
    got = jnp.zeros_like(want)
    for chip in range(4):
        held = slice(2 * chip, 2 * chip + 2)
        ctx = _tiny_ctx()
        stacks = [apc.APExpertStack.from_dense(p[n][held],
                                               store=ctx.runtime.pool.resident)
                  for n in ("w1", "w3", "w2")]
        got = got + apc.ap_moe_dispatch(ctx, u, ids, gates, *stacks,
                                        jax.nn.silu, first=held.start)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


def test_padded_pairs_change_only_the_rows_launched():
    """3 held pairs launch as 4 (``pair_slots``): the padding row takes no
    gate, so the layer equals the reference's over the held experts; the
    counters and the record count the 3 real pairs.  A call with no held
    pair launches the capacity's zero rows and returns zeros."""
    config = tiny_config(held_experts=[0, 4])
    m = qwen3_moe.dims(config)
    p = qwen3_moe.weights(config, SEED)["layers"][0]
    u = jax.random.normal(jax.random.PRNGKey(5), (1, m["d"]), jnp.float32)
    ids = jnp.asarray([[2, 1, 6, 3]], jnp.int32)        # 3 of 4 held
    probs = jax.nn.softmax(u @ p["router"], axis=-1)
    gates = jnp.take_along_axis(probs, ids, axis=1)
    gates = gates / gates.sum(-1, keepdims=True)
    given = (jnp.full((1, m["d"]), qwen3.NOT_GIVEN, jnp.int32),
             jnp.full((1, 4, m["ff"]), qwen3.NOT_GIVEN, jnp.int32), ids)
    mm = lambda a, b: jnp.matmul(a, b, precision=qwen3.HIGHEST)  # noqa
    want, _, _ = qwen3_moe.moe(p, u, m, 7, mm, *given, 0.0, 1.0)
    ctx = _tiny_ctx()
    stacks = [apc.APExpertStack.from_dense(p[n],
                                           store=ctx.runtime.pool.resident)
              for n in ("w1", "w3", "w2")]
    records = []
    ctx.on_moe = records.append
    before = counters()
    got = apc.ap_moe_dispatch(ctx, u, ids, gates, *stacks, jax.nn.silu)
    count = delta(before)
    assert apc.pair_slots(3, 0, 4) == 4 and apc.pair_slots(1, 0, 8) == 1
    assert apc.pair_slots(0, 3, 8) == 4 and apc.pair_slots(5, 3, 8) == 8
    assert apc.pair_slots(3, 0, 3) == 3
    assert records[0]["launched"] == 4 and len(records[0]["pairs"]) == 3
    assert records[0]["h_levels"].shape == (4, m["ff"])
    assert count["moe.pairs_held"] == 3 and count["moe.pairs_routed"] == 4
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)
    before = counters()
    outside = jnp.asarray([[4, 5, 6, 7]], jnp.int32)
    none = apc.ap_moe_dispatch(ctx, u, outside, gates, *stacks, jax.nn.silu,
                               capacity=2)
    count = delta(before)
    assert records[1]["launched"] == 2 and len(records[1]["pairs"]) == 0
    assert count["moe.layers_empty"] == 1 and count["pool.launches"] > 0
    assert not np.any(np.asarray(none))


def test_grouped_mac_digits_equal_per_expert_dispatch():
    """Every row of a grouped MAC (rows of 3 experts in one schedule)
    carries the same accumulator digits as that expert's own projection
    on the same integer inputs; the grouped schedule's union support
    covers each expert's."""
    rng = np.random.default_rng(4)
    e, k, n = 3, 12, 5
    w = rng.normal(0, 1, (e, k, n)) * (rng.random((e, k, n)) < 0.5)
    ctx = _tiny_ctx()
    stack = apc.APExpertStack.from_dense(jnp.asarray(w, jnp.float32))
    x = jnp.asarray(rng.integers(-7, 8, (6, k)), jnp.int32)
    experts = np.array([2, 0, 2, 1, 0, 2])
    g = apc.ProgramGraph()
    call = stack.add_call(g, x, experts, max_cols=ctx.max_cols, max_q=7)
    grouped = np.asarray(ctx.runtime.run_graph(g)[call.node])
    for ex in range(e):
        lin = apc.APLinear.from_dense(jnp.asarray(w[ex], jnp.float32))
        assert set(np.flatnonzero(np.asarray(lin._support) & ~np.asarray(
            stack._support))) == set()
        rows = np.flatnonzero(experts == ex)
        g1 = apc.ProgramGraph()
        c1 = lin.add_call(g1, x[rows], max_cols=ctx.max_cols, max_q=7)
        own = np.asarray(ctx.runtime.run_graph(g1)[c1.node])
        for j, p in enumerate(rows):
            np.testing.assert_array_equal(grouped[p * n:(p + 1) * n],
                                          own[j * n:(j + 1) * n])
    y = call.decode({call.node: jnp.asarray(grouped)}, jnp.ones(6))
    w_ter, scale = jax.vmap(qwen3.ternarize)(jnp.asarray(w, jnp.float32))
    want = np.einsum("pk,pkn->pn", np.asarray(x), np.asarray(w_ter)[experts]
                     ) * np.asarray(scale)[experts]
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-6)


def test_two_request_wave_merges_grouped_calls():
    """Two requests of one wave, each routed to other held experts: their
    grouped calls share one schedule and one resident plane, so the wave
    runs each node once over both requests' rows."""
    from repro.serve.batcher import WaveMerger
    rng = np.random.default_rng(6)
    ctx = _tiny_ctx()
    stacks = [apc.APExpertStack.from_dense(
        jnp.asarray(rng.normal(0, 1, shape), jnp.float32), label=f"w{i}",
        store=ctx.runtime.pool.resident)
        for i, shape in enumerate([(3, 8, 6), (3, 8, 6), (3, 6, 8)])]
    for st in stacks:          # compile first: lru misses race in threads
        st.tiled(ctx.max_cols, ctx.x_levels)
    merger = WaveMerger(ctx.runtime, 2)
    xs = jnp.asarray(rng.normal(0, 1, (2, 1, 8)), jnp.float32)
    out = [None, None]

    def request(slot, ids):
        merger.bind(slot)
        x = xs[slot]
        with apc.ap_request_scope(apc.APSink(), merger):
            out[slot] = apc.ap_moe_dispatch(
                ctx, x, jnp.asarray([ids], jnp.int32),
                jnp.asarray([[0.6, 0.4]], jnp.float32), *stacks, jax.nn.silu)

    threads = [threading.Thread(target=request, args=a)
               for a in ((0, [0, 2]), (1, [1, 5]))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all(o is not None for o in out)
    assert merger.n_merged_runs == 2
    assert 2 * merger.merged_nodes == merger.source_nodes


def test_share_config_matches_the_published_config():
    from repro.configs import get_config, get_share_config
    full = get_config("qwen3-moe-30b-a3b")
    assert (full.d_model, full.n_layers, full.vocab) == (2048, 48, 151936)
    assert not full.tie_embeddings and full.norm_eps == 1e-6
    assert full.moe.norm_topk and full.moe.held is None
    share = get_share_config("qwen3-moe-30b-a3b", 1)
    assert share.moe.held == (32, 64) and share.moe.n_held == 32
    assert share.with_(moe=full.moe) == full
    with pytest.raises(ValueError):
        MoECfg(n_experts=8, top_k=2, d_ff=4, held=(6, 10))


def test_smoke_share_is_served_only_on_the_bank(smoke_mesh):
    """The smoke configuration's share holds 2 of its 8 experts and routes
    over all 8.  It decodes on the bank, where the served parameters keep
    no float expert weights, and refuses to run without an AP context."""
    from repro.configs.qwen3_moe_30b_a3b import smoke_share_config
    cfg = smoke_share_config(3)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    moe = params["stack"]["pos_0"]["moe"]
    assert moe["router"].shape[-1] == 8 and moe["w1"].shape[1] == 2
    cache = M.init_cache(cfg, 1, 8)
    args = (cache, jnp.ones((1,), jnp.int32), jnp.int32(0), smoke_mesh)
    with smoke_mesh, pytest.raises(ValueError, match="AP bank"):
        M.decode_step(cfg, params, *args)
    ctx = _tiny_ctx()
    served = M.prepare_ap(cfg, params, ctx)
    assert set(served["stack"]["pos_0"]["moe"]) == {"router"}
    assert ctx.cache_stats()["stacks"] == 3 * cfg.n_layers
    before = counters()
    with smoke_mesh, apc.ap_serving(ctx):
        logits, _ = M.decode_step(cfg, served, *args)
    count = delta(before)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())
    assert count.get("moe.layer_calls", 0) \
        + count.get("moe.layers_empty", 0) == cfg.n_layers
