"""Driver of AP-backed sparse-expert serving: closed-loop clients through
``BatchServer``, one chip's share of the experts on the bank.

As ``serve.py`` (whose window, warm-up and step recording it reuses), for a
Qwen3-MoE configuration: set-up builds the program's ``ModelConfig`` with the
share of experts the configuration holds (``held_experts``), makes the weights
but the experts on the device from the seed in one jitted call (the
reference's float32 weights; embedding and head in the configuration's
``torch_dtype``), then makes each layer's held experts in float32 and
converts them for the bank, one layer at a time, and serves through
``Engine``.  Warm-up runs one model step outside the server, one MoE layer
call of every shape a lone or merged wave can give (``warm_calls``: a call
launches its held pairs padded to one of a few counts, at least the held
experts' capacity), then ``serve.warm``'s lone
and merged waves, so that nothing compiles in the window.

Every step of the window is recorded: its logits (``serve``'s wrapper) and,
per MoE layer, the program's dispatch record (``APServeContext.on_moe``):
the routed experts, the token's integer levels and those of each held pair's
hidden row.  Kernel work comes from the rows each layer call launched.

The check rebuilds the float32 weights from the seed after the program's
state is freed and runs the plain reference (``reference/qwen3_moe.py``) over
every request: the widest relative logit gap, served tokens against greedy
picking, routes that differ outside the route tie, and every layer of every
step on the bank (each step's dispatch records, and the ``moe.layer_calls``
and ``moe.layers_empty`` counters against layers x tokens).
"""
from __future__ import annotations

import gc
import sys
import threading
import time
from pathlib import Path

import numpy as np

import run
import work
from reference import qwen3, qwen3_moe

serve = run.load_module(Path(__file__).with_name("serve.py"))


def model_config(config: dict):
    """The program's ModelConfig of a Qwen3-MoE configuration file: the
    router over ``router_width`` experts, the held share of them and its
    capacity."""
    from repro.configs.base import ModelConfig, MoECfg
    m = qwen3_moe.dims(config)
    moe = MoECfg(n_experts=m["router"], top_k=m["k"], d_ff=m["ff"],
                 norm_topk=m["norm_topk"],
                 capacity_factor=float(config["assumed"]["capacity_factor"]),
                 held=(m["first"], m["first"] + m["held"]))
    return ModelConfig(
        name=config.get("name", config["model_type"]), family="moe",
        n_layers=m["layers"], d_model=m["d"], n_heads=m["h"],
        n_kv_heads=m["hk"], head_dim=m["hd"], d_ff=m["ff"], vocab=m["v"],
        qk_norm=True, tie_embeddings=bool(config["tie_word_embeddings"]),
        rope_theta=m["theta"], norm_eps=m["eps"], act=config["hidden_act"],
        compute_dtype=config["serving"]["compute_dtype"],
        ffn_pattern=("moe",), moe=moe, remat="none")


def program_params(config: dict, seed: int):
    """The served parameter tree but the experts, made on the device in one
    jitted call: the reference's weights in the program's layout (layers
    stacked on a leading axis), embedding and head in ``torch_dtype``."""
    import jax
    import jax.numpy as jnp
    dt = jnp.dtype(config["torch_dtype"])
    make = qwen3_moe.make_weights(config, with_experts=False)

    @jax.jit
    def served(key):
        w = make(key)
        stack = lambda n: jnp.stack([p[n] for p in w["layers"]])  # noqa
        block = {"norm1": stack("norm1"), "norm2": stack("norm2"),
                 "attn": {n: stack(n) for n in ("wq", "wk", "wv", "wo",
                                                "q_norm", "k_norm")},
                 "moe": {"router": stack("router")}}
        return {"embed": {"table": w["embed"].astype(dt)},
                "lm_head": {"w": w["head"].astype(dt)},
                "final_norm": w["final_norm"], "stack": {"pos_0": block}}

    return served(qwen3.seed_key(seed))


def convert_experts(config: dict, seed: int, ctx) -> None:
    """Each layer's held experts, made on the device in float32 from the
    seed and converted for the bank at once (``prepare_moe_ap``), one layer
    at a time: the bank's planes are all that stays."""
    import jax
    from repro.models.moe import prepare_moe_ap
    make = jax.jit(qwen3_moe.make_experts(config))
    key = qwen3.seed_key(seed)
    for layer in range(qwen3_moe.dims(config)["layers"]):
        prepare_moe_ap(ctx, layer, make(key, layer))


def setup(config: dict, traffic: dict, seed: int) -> dict:
    t0 = time.perf_counter()
    state = build(config, traffic, seed)
    print(f"serve_moe: built in {time.perf_counter() - t0:.1f} s (weights, "
          f"expert conversion)", file=sys.stderr, flush=True)
    warm_step(state)
    warm_calls(state)
    t1 = time.perf_counter()
    serve.warm(state, int(traffic["clients"]))
    print(f"serve_moe: warm-up waves {time.perf_counter() - t1:.1f} s",
          file=sys.stderr, flush=True)
    return state


def build(config: dict, traffic: dict, seed: int) -> dict:
    """The served system, recording every step, not yet warmed."""
    import jax
    from repro import apc
    from repro.serve.batcher import AdmissionCfg, BatchServer
    from repro.serve.engine import Engine, ServeCfg
    cfg = model_config(config)
    params = program_params(config, seed)
    jax.block_until_ready(params)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1),
                             ("pod", "data", "model"))
    pool = apc.ArrayPool(**config["assumed"]["bank"])
    ctx = apc.APServeContext(serve._annotated_runtime(pool),
                             x_levels=int(config["assumed"]["x_levels"]))
    convert_experts(config, seed, ctx)
    eng = Engine(cfg, params, mesh,
                 ServeCfg(max_len=int(config["max_position_embeddings"])),
                 ap_ctx=ctx)
    clients = int(traffic["clients"])
    srv = BatchServer(eng, admission=AdmissionCfg(max_inflight=clients))
    state = {"config": config, "traffic": traffic, "seed": seed, "cfg": cfg,
             "srv": srv, "engine": eng, "pool": pool,
             "close_at": float("inf"), "requests": [], "wave_spans": {}}
    serve._record_steps(state)
    _record_moe(state)
    return state


def _record_moe(state: dict) -> None:
    """Keep, per step of every request, the program's MoE dispatch records
    (in the order of the step's layers), on top of ``serve``'s recording."""
    eng = state["engine"]
    make, here = eng.new_request, threading.local()

    def on_moe(record):
        rec = getattr(here, "rec", None)
        if rec is not None:
            rec["moe"][here.pos].append(record)

    def new_request(*args, **kw):
        req = make(*args, **kw)
        rec = next(r for r in reversed(state["requests"])
                   if r["request"] is req)
        rec["moe"] = {}
        step = req.step

        def moe_step():
            rec["moe"][req.pos] = []
            here.rec, here.pos = rec, req.pos
            try:
                return step()
            finally:
                here.rec = None

        req.step = moe_step
        return req

    eng.new_request = new_request
    eng.ap_ctx.on_moe = on_moe


def warm_step(state: dict) -> None:
    """One model step outside the server (its compiles run under no
    request's timeout)."""
    cfg = state["cfg"]
    t0 = time.perf_counter()
    state["engine"].generate(serve.prompt_for(0, 0, 1, cfg.vocab)[None], 1)
    state["engine"].ap_ctx.reset()
    print(f"serve_moe: one model step {time.perf_counter() - t0:.1f} s",
          file=sys.stderr, flush=True)


def warm_calls(state: dict) -> None:
    """Run one MoE layer call of every shape a wave can give, lone and
    merged, outside the server: a call launches its held pairs padded to
    ``pair_slots`` (at least the held experts' capacity), so a lone wave
    has one of a few row counts per call and a merged wave one of their
    ordered pairs (a model step is one token, so at most ``k`` pairs).
    Each call routes the one token to as many held experts as give that
    count, through a ``WaveMerger`` of the wave's width and under the
    engine's mesh, as the batcher does."""
    import jax
    import jax.numpy as jnp
    from repro import apc
    from repro.apc.layers import pair_slots
    from repro.models.moe import expert_key, held_capacity
    from repro.serve.batcher import WaveMerger
    t0 = time.perf_counter()
    m = qwen3_moe.dims(state["config"])
    ctx = state["engine"].ap_ctx
    capacity = held_capacity(state["cfg"].moe, 1)
    stacks = [ctx.expert_stack(expert_key(0, n)) for n in ("w1", "w3", "w2")]
    for st in stacks:           # compile first: lru misses race in threads
        st.tiled(ctx.max_cols, ctx.x_levels)
    held_for: dict[int, int] = {}      # launched pairs -> held pairs giving it
    for n in range(m["k"] + 1):
        held_for.setdefault(pair_slots(n, capacity, m["k"]), n)
    # inputs placed as the model's activations are (replicated over the
    # engine's mesh): the programs compiled for them are the ones served
    on_mesh = jax.sharding.NamedSharding(state["engine"].mesh,
                                         jax.sharding.PartitionSpec())
    x = jax.device_put(jax.random.normal(jax.random.PRNGKey(0),
                                         (1, m["d"]), jnp.float32), on_mesh)
    gates = jax.device_put(jnp.full((1, m["k"]), 1.0 / m["k"], jnp.float32),
                           on_mesh)
    outside = [e for e in range(m["router"])
               if not m["first"] <= e < m["first"] + m["held"]]

    def call(slot, merger, n):
        ids = [m["first"] + e for e in range(n)] + outside[:m["k"] - n]
        merger.bind(slot)
        sink = apc.APSink(radix=ctx.radix)
        with state["engine"].mesh, apc.ap_serving(ctx), \
                apc.ap_request_scope(sink, merger):
            apc.ap_moe_dispatch(
                ctx, x, jax.device_put(jnp.asarray([ids], jnp.int32),
                                       on_mesh),
                gates, *stacks, jax.nn.silu, first=m["first"],
                capacity=capacity, layer=0)
        sink.flush()

    held = list(held_for.values())
    waves = [(n,) for n in held] + [(a, b) for a in held for b in held]
    for wave in waves:
        merger = WaveMerger(ctx.runtime, len(wave))
        threads = [threading.Thread(target=call, args=(i, merger, n))
                   for i, n in enumerate(wave)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    ctx.reset()
    print(f"serve_moe: warmed {len(waves)} layer calls launching "
          f"{sorted(held_for)} pairs in {time.perf_counter() - t0:.1f} s",
          file=sys.stderr, flush=True)


def call_work(state: dict, layer: int, pairs: int) -> dict:
    """Kernel work of one call of MoE layer ``layer`` that launched
    ``pairs`` (padded) pairs: the gate and up grouped MACs over pairs x
    d_ff rows, the down MAC over pairs x d_model rows, from the schedules
    the program compiles for them."""
    from repro.models.moe import expert_key
    ctx, pool = state["engine"].ap_ctx, state["pool"]
    total: dict = {}
    for name in ("w1", "w3", "w2"):
        stack = ctx.expert_stack(expert_key(layer, name))
        tiled = stack.tiled(ctx.max_cols, ctx.x_levels)
        for prog in tiled.programs + tiled.reduce_programs:
            work.add_work(total, work.run_work(prog, pairs * stack.n,
                                               pool.rows, prog.min_cols))
    return total


def window(state: dict, seconds: float) -> dict:
    facts = serve.window(state, seconds)
    cfg = state["cfg"]
    calls: dict[tuple[int, int], int] = {}       # (layer, pairs) -> calls
    for r in state["requests"]:
        for pos in r["logits"]:
            for rec in r["moe"][pos]:
                key = (rec["layer"], rec["launched"])
                calls[key] = calls.get(key, 0) + 1
    kernel: dict = {}
    for (layer, p), n in calls.items():
        work.add_work(kernel, call_work(state, layer, p), n)
    m = qwen3_moe.dims(state["config"])
    flops = sum((work.attention_flops(cfg.d_model, cfg.n_heads,
                                      cfg.n_kv_heads, cfg.head_dim_, t + 1)
                 + 2 * cfg.d_model * m["router"]) * cfg.n_layers
                + work.head_flops(cfg.d_model, cfg.vocab)
                for r in state["requests"] for t in range(len(r["logits"])))
    facts.update(kernel_work=kernel, float_flops=flops)    # serve's are dense
    by_pairs: dict[int, int] = {}
    for (_, p), n in calls.items():
        by_pairs[p] = by_pairs.get(p, 0) + n
    print(f"serve_moe: layer calls by pairs launched "
          f"{sorted(by_pairs.items())},"
          f" launches from their rows {kernel.get('launches', 0)}",
          file=sys.stderr)
    return facts


def served_steps(state: dict) -> list[dict]:
    """Per request with a step in the window: its input tokens, the logits
    of each step, its served tokens with the step that produced each, the
    program's choices as the reference's ``given`` (x levels [L, n, d], h
    levels [L, n, E, ff], routes [L, n, k]) and the steps whose MoE layers
    did not all run on the bank."""
    config = state["config"]
    m = qwen3_moe.dims(config)
    out = []
    for r in state["requests"]:
        n, req = len(r["logits"]), r["request"]
        if n == 0:
            continue
        if sorted(r["logits"]) != list(range(n)):
            raise RuntimeError(f"steps {sorted(r['logits'])} are not the "
                               f"first {n} of the request")
        served = [int(np.asarray(t).reshape(-1)[0]) for t in req.out]
        seq = np.concatenate([np.asarray(req.prompts).reshape(-1),
                              np.asarray(served, np.int32)])[:n]
        logits = np.stack([np.asarray(r["logits"][p], np.float32)
                           .reshape(-1) for p in range(n)])
        given = qwen3_moe.not_given(config, n)
        off_bank = 0
        for pos in range(n):
            recs = r["moe"].get(pos, [])
            if sorted(rec["layer"] for rec in recs) != list(
                    range(m["layers"])):
                off_bank += 1
            for rec in recs:
                i = rec["layer"]
                given[2][i, pos] = np.asarray(rec["experts"]).reshape(-1)
                given[0][i, pos] = np.asarray(rec["x_levels"]).reshape(-1)
                h = np.asarray(rec["h_levels"])
                for row, (_, e) in enumerate(rec["pairs"]):
                    given[1][i, pos, e - m["first"]] = h[row]
        out.append({"tokens": seq.astype(np.int32), "logits": logits,
                    "given": given, "off_bank": off_bank, "served": served,
                    "served_at": [req.s_prompt - 1 + j
                                  for j in range(len(served))]})
    return out


def check(state: dict, facts: dict, seed: int, *, precision: str = "f32"
          ) -> list[dict]:
    """The program's logits of every step against the plain float32
    reference, its served tokens against greedy picking, its routes
    against the reference's outside the route tie, and every layer of
    every step on the bank.  The reference takes the program's level or
    route at a tie.  With ``precision`` (the control) the reference at
    that precision stands in the program's place, read the same way; the
    program's readings stand beside it under ``program``."""
    served = served_steps(state)
    srv = state.pop("srv", None)
    if srv is not None:
        srv.close()
    for k in ("engine", "pool", "requests"):       # the program's state
        state.pop(k, None)
    gc.collect()
    return compare(state["config"], seed, served, facts["counters"],
                   len(state.get("errors", ())), precision)


def compare(config: dict, seed: int, served: list, counters: dict,
            failed: int = 0, precision: str = "f32") -> list[dict]:
    """``check``'s readings of the steps ``served`` (``served_steps``) and
    the program's counters over them."""
    w = qwen3_moe.weights(config, seed)
    levels = int(config["assumed"]["x_levels"])
    length = int(config["max_position_embeddings"])
    tie = float(config["check"]["level_tie"])
    route_tie = float(config["check"]["route_tie"])
    sides = {"program": {"gaps": [0.0], "misses": 0, "not_taken": 0,
                         "routes_taken": 0, "routes_off": 0, "steps": 0}}
    if precision != "f32":
        sides["control"] = {k: (list(v) if isinstance(v, list) else v)
                            for k, v in sides["program"].items()}
    for r in served:
        toks, at = r["tokens"], r["served_at"]
        got = {"program": (r["logits"], r["given"], r["served"])}
        if precision != "f32":
            low, lv, _ = qwen3_moe.prefix_logits(w, config, toks, levels,
                                                 length, precision)
            got["control"] = (low, lv, [int(low[p].argmax()) for p in at])
        for side, (lg, given, tokens) in got.items():
            ref, _, counts = qwen3_moe.prefix_logits(
                w, config, toks, levels, length, given=given, tie=tie,
                route_tie=route_tie)
            v = sides[side]
            v["gaps"].extend(qwen3.rel_gaps(lg, ref))
            v["misses"] += qwen3.greedy_misses(lg, tokens, at)
            v["steps"] += len(toks)
            for c in ("not_taken", "routes_taken", "routes_off"):
                v[c] += counts[c]
    m = qwen3_moe.dims(config)
    steps = sum(len(r["tokens"]) for r in served)
    on_bank = (counters.get("moe.layer_calls", 0)
               + counters.get("moe.layers_empty", 0))
    off_bank = (sum(r["off_bank"] for r in served)
                + abs(m["layers"] * steps - on_bank))
    for side, v in sides.items():
        print(f"serve_moe: {side}: {len(v['gaps']) - 1} steps compared, "
              f"widest logit gaps {sorted(v['gaps'])[-3:]}, levels off by "
              f"more than a tie {v['not_taken']}, routes taken at a tie "
              f"{v['routes_taken']} of {v['steps'] * m['layers']}, routes "
              f"off {v['routes_off']}", file=sys.stderr)
    print(f"serve_moe: layer-steps on the bank {on_bank} of "
          f"{m['layers'] * steps}; launches counted "
          f"{counters.get('pool.launches', 0)}", file=sys.stderr)
    read = sides.get("control", sides["program"])
    prog = sides["program"]
    checks = [{"name": "logit_rel_gap", "value": max(read["gaps"]),
               "limit": config["check"]["logit_rel_gap"]},
              {"name": "served_not_greedy", "value": read["misses"],
               "limit": 0},
              {"name": "routes_off", "value": read["routes_off"],
               "limit": 0},
              {"name": "layer_steps_off_bank", "value": off_bank,
               "limit": 0},
              {"name": "failed_requests", "limit": 0, "value": failed}]
    if precision != "f32":
        checks[0]["program"] = max(prog["gaps"])
        checks[1]["program"] = prog["misses"]
        checks[2]["program"] = prog["routes_off"]
    return checks
