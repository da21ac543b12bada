"""Driver of AP-backed serving: closed-loop clients through ``BatchServer``.

Set-up builds the configuration's model in the program (its ``ModelConfig``
with ternary MLPs), makes the weights on the device from the seed in one
jitted call (the reference's float32 weights, packed by the program's own
serving conversion), puts the MLPs on an ``ArrayPool`` of the assumed
geometry behind ``APServeContext``, starts one ``BatchServer``, and warms
lone and merged waves, prefill and decode (``warm``).  The window drives the mix:
each client submits a request, waits for its tokens, and submits the next.

A model step costs seconds, so the window is counted in waves, not
requests: it ends with the wave in flight at ``--seconds``.  Each request's
``step`` (the call the batcher makes for it in every wave) is wrapped: it
keeps the logits that the step produced, and in a wave that would start
after the close it raises ``WindowClosed`` before stepping, so that wave
runs nothing and cuts every request still in flight.  Tokens are the model
steps of the window's waves.

The check stops the server, frees the program's state, rebuilds the float32
weights from the seed and runs the plain reference over every request's
prompt and served tokens, as far as its steps went: the widest relative
gap between a step's logits and the reference's is compared with the
configuration's limit, and every served token has to be the greedy pick of
its step's logits.  The wrapped step also keeps the integer levels that the
step's MLPs were given; where one lies a step from the reference's own at
a tie (an input within ``check.level_tie`` of a rounding boundary), the
reference takes it, so that the gap reads the precision of the step and
not which way a tie fell.
"""
from __future__ import annotations

import gc
import sys
import threading
import time

import numpy as np

import loadgen
import work
from reference import qwen3


def model_config(config: dict):
    """The program's ModelConfig of a Qwen3 configuration file."""
    from repro.configs.base import ModelConfig, TernaryCfg
    m = qwen3.dims(config)
    return ModelConfig(
        name=config.get("name", config["model_type"]), family="dense",
        n_layers=m["layers"], d_model=m["d"], n_heads=m["h"],
        n_kv_heads=m["hk"], head_dim=m["hd"], d_ff=m["ff"], vocab=m["v"],
        qk_norm=True, tie_embeddings=bool(config["tie_word_embeddings"]),
        rope_theta=m["theta"], norm_eps=m["eps"], act=config["hidden_act"],
        compute_dtype=config["serving"]["compute_dtype"],
        ternary=TernaryCfg(enabled=True))


def program_params(config: dict, seed: int):
    """The served parameter tree, made on the device in one jitted call:
    the reference's weights in the program's layout (one pattern period,
    layers stacked on a leading axis), MLPs packed to ternary by the
    program's serving conversion."""
    import jax
    import jax.numpy as jnp
    from repro.models.quant import quantize_model_params

    @jax.jit
    def make(w):
        stack = lambda n: jnp.stack([p[n] for p in w["layers"]])  # noqa
        block = {"norm1": stack("norm1"), "norm2": stack("norm2"),
                 "attn": {n: stack(n) for n in ("wq", "wk", "wv", "wo",
                                                "q_norm", "k_norm")},
                 "mlp": {n: stack(n) for n in ("w1", "w3", "w2")}}
        return quantize_model_params({
            "embed": {"table": w["embed"]}, "final_norm": w["final_norm"],
            "stack": {"pos_0": block}})

    return make(qwen3.weights(config, seed))


def _annotated_runtime(pool):
    """The program's Runtime, with each graph run marked in the profiler's
    trace (the idle-gap attribution reads the marks)."""
    import jax
    from repro.apc.runtime import Runtime

    class AnnotatedRuntime(Runtime):
        def run_graph(self, *args, **kw):
            with jax.profiler.TraceAnnotation("bench.run_graph"):
                return super().run_graph(*args, **kw)

    return AnnotatedRuntime(pool)


def setup(config: dict, traffic: dict, seed: int) -> dict:
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
    ctx = apc.APServeContext(_annotated_runtime(pool),
                             x_levels=int(config["assumed"]["x_levels"]))
    eng = Engine(cfg, params, mesh,
                 ServeCfg(max_len=int(config["max_position_embeddings"])),
                 ap_ctx=ctx)
    clients = int(traffic["clients"])
    srv = BatchServer(eng, admission=AdmissionCfg(max_inflight=clients))
    state = {"config": config, "traffic": traffic, "seed": seed, "cfg": cfg,
             "srv": srv, "engine": eng, "pool": pool,
             "close_at": float("inf"), "requests": [], "wave_spans": {}}
    _record_steps(state)
    warm(state, clients)
    return state


class WindowClosed(Exception):
    """A request cut at the window's close: its next wave would start after
    ``--seconds``."""


def _record_steps(state: dict) -> None:
    """Wrap the step of every request the engine makes: keep, by position,
    the logits of each step and the integer levels its MLPs were given
    (each call of the AP context's ``quantize`` in the step's thread), and
    run no step in a wave that starts after ``state["close_at"]``.  The
    first step of a wave decides for the whole wave (the batcher's wave
    counter is the same for all of its steps)."""
    eng, srv = state["engine"], state["srv"]
    ctx = eng.ap_ctx
    make, quantize = eng.new_request, ctx.quantize
    lock, wave_open, here = threading.Lock(), {}, threading.local()

    def quantize_seen(x):
        out = quantize(x)
        rec = getattr(here, "rec", None)
        if rec is not None:
            rec["levels"][here.pos].append(out[0])
        return out

    def new_request(*args, **kw):
        req = make(*args, **kw)
        rec = {"request": req, "logits": {}, "levels": {}, "waves": set()}
        step = req.step

        def window_step():
            wave = srv.n_waves
            with lock:
                if wave not in wave_open:
                    wave_open[wave] = time.perf_counter() < state["close_at"]
            if not wave_open[wave]:
                raise WindowClosed(f"window closed before wave {wave}")
            pos, t0 = req.pos, time.perf_counter()
            rec["levels"][pos] = []
            here.rec, here.pos = rec, pos
            try:
                done = step()
            finally:
                here.rec = None
            rec["logits"][pos] = req.logits
            rec["waves"].add(wave)
            with lock:
                spans = state["wave_spans"].setdefault(wave, [t0, t0])
                spans[0] = min(spans[0], t0)
                spans[1] = max(spans[1], time.perf_counter())
            return done

        req.step = window_step
        state["requests"].append(rec)
        return req

    eng.new_request = new_request
    ctx.quantize = quantize_seen


def wave_widths(recs: list) -> list[int]:
    """Requests stepped in each wave that the records saw, in wave order."""
    count: dict[int, int] = {}
    for r in recs:
        for w in r["waves"]:
            count[w] = count.get(w, 0) + 1
    return [count[w] for w in sorted(count)]


def warm(state: dict, clients: int) -> None:
    """Warm every shape the window uses.  A lone one-step request first: it
    pins the MLP weights on the bank, and every later wave runs on pinned
    weights, which merge into other shapes.  Then a request of two steps
    and ``clients - 1`` of one step, which give a merged wave (as wide as
    the clients) and a lone one, prefill and decode; without both, that
    round runs again.  The prompts differ (requests alike merge into other
    shapes than requests that differ)."""
    srv, timeout = state["srv"], state["traffic"]["request_timeout_s"]
    vocab = state["cfg"].vocab
    srv.submit(prompt_for(0, 0, 1, vocab)[None], 1).result(timeout=timeout)
    for attempt in range(3):
        state["requests"] = []
        hs = [srv.submit(prompt_for(0, 1 + attempt * clients + c, 1, vocab)
                         [None], 1 if c else 2) for c in range(clients)]
        for h in hs:
            h.result(timeout=timeout)
        widths = wave_widths(state["requests"])
        print(f"serve: warm-up waves of {widths} requests", file=sys.stderr)
        if {1, clients} <= set(widths):
            return
    raise RuntimeError("warm-up never saw a merged and a lone wave")


def prompt_for(seed: int, index: int, length: int, vocab: int) -> np.ndarray:
    """Prompt token ids of job ``index``: uniform over the vocabulary
    (id 0 is the padding id), from the seed."""
    rng = np.random.default_rng([seed, index])
    return rng.integers(1, vocab, size=length).astype(np.int32)


def step_work(state: dict) -> dict:
    """Kernel work of one token through the model step: every AP program
    that the served MLPs launch (gate and up over d_model, down over d_ff),
    from the schedules the program compiles for them."""
    from repro.apc.mac import compile_mac_tiled, mac_acc_width
    from repro.kernels.ternary_matmul.ap import default_k_tile
    cfg, pool = state["cfg"], state["pool"]
    levels = int(state["config"]["assumed"]["x_levels"])
    total: dict = {}
    for k, n, times in ((cfg.d_model, cfg.d_ff, 2), (cfg.d_ff, cfg.d_model, 1)):
        kp = -(-k // 16) * 16                      # packed K, as served
        width = mac_acc_width(3, kp, levels)
        tiled = compile_mac_tiled(
            3, kp, width, min(default_k_tile(pool.cols, width), kp),
            max_cols=pool.cols)
        for prog in tiled.programs + tiled.reduce_programs:
            work.add_work(total, work.run_work(prog, n, pool.rows,
                                               prog.min_cols),
                          times * cfg.n_layers)
    return total


def window(state: dict, seconds: float) -> dict:
    import jax
    srv, cfg, seed = state["srv"], state["cfg"], state["seed"]
    timeout = state["traffic"]["request_timeout_s"]
    errors = []

    def job(i, j):
        prompt = prompt_for(seed, i, j["prompt_tokens"], cfg.vocab)
        with jax.profiler.TraceAnnotation("bench.request"):
            h = srv.submit(prompt[None], j["new_tokens"])
            try:
                h.result(timeout=timeout)
            except WindowClosed:
                pass                           # cut at the close
            except Exception as e:             # a failed request counts
                errors.append(repr(e))

    state["requests"], state["wave_spans"] = [], {}
    t0 = time.perf_counter()
    state["close_at"] = t0 + seconds
    _, window_s = loadgen.closed_loop(state["traffic"], seed, seconds, job)
    print("serve: waves (start, end) in the window: " + ", ".join(
        f"({a - t0:.2f}, {b - t0:.2f})"
        for a, b in sorted(state["wave_spans"].values())), file=sys.stderr)
    recs = state["requests"]
    steps = [len(r["logits"]) for r in recs]
    tokens = sum(steps)
    waves = len(set().union(*(r["waves"] for r in recs)))
    per_token = step_work(state)
    flops = sum(work.attention_flops(cfg.d_model, cfg.n_heads,
                                     cfg.n_kv_heads, cfg.head_dim_, t + 1)
                * cfg.n_layers + work.head_flops(cfg.d_model, cfg.vocab)
                for n in steps for t in range(n))
    state["errors"] = errors
    return {"window_s": window_s, "tokens": tokens, "requests": len(recs),
            "waves": waves,
            "kernel_work": {k: v * tokens for k, v in per_token.items()},
            "float_flops": flops, "attempted": tokens + len(errors),
            "failed": len(errors)}


def served_steps(recs: list) -> list[dict]:
    """Per request with a step in the window: its input tokens, the logits
    of each step (float32, on the host), the levels its MLPs were given
    (``(y, a)``, each [layers, steps, width]) and its served tokens with
    the step that produced each."""
    out = []
    for r in recs:
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
        calls = [r["levels"][p] for p in range(n)]
        if len({len(c) for c in calls}) != 1 or len(calls[0]) % 2:
            raise RuntimeError(f"MLP level calls per step: "
                               f"{[len(c) for c in calls]}")
        levels = tuple(                          # (y, a): [layers, n, .]
            np.stack([np.stack([np.asarray(c[2 * i + j]).reshape(-1)
                                for c in calls])
                      for i in range(len(calls[0]) // 2)])
            for j in (0, 1))
        out.append({"tokens": seq.astype(np.int32), "logits": logits,
                    "levels": levels,
                    "served": served,
                    "served_at": [req.s_prompt - 1 + j
                                  for j in range(len(served))]})
    return out


def check(state: dict, facts: dict, seed: int, *, precision: str = "f32"
          ) -> list[dict]:
    """The program's logits of every step of the window against the plain
    float32 reference, and its served tokens against greedy picking.  The
    reference takes the program's MLP level where it differs from its own
    at a tie (``check.level_tie``).  With ``precision`` (the control) the
    reference at that precision stands in the program's place, read the
    same way: its readings are checked, and the program's stand beside
    them under ``program``."""
    config = state["config"]
    served = served_steps(state["requests"])
    srv = state.pop("srv", None)
    if srv is not None:
        srv.close()
    for k in ("engine", "pool", "requests"):       # the program's state
        state.pop(k, None)
    gc.collect()
    w = qwen3.weights(config, seed)
    levels = int(config["assumed"]["x_levels"])
    length = int(config["max_position_embeddings"])
    tie = float(config["check"]["level_tie"])
    sides = {"program": {"gaps": [0.0], "misses": 0, "not_taken": 0}}
    if precision != "f32":
        sides["control"] = {"gaps": [0.0], "misses": 0, "not_taken": 0}
    for r in served:
        toks, at = r["tokens"], r["served_at"]
        got = {"program": (r["logits"], r["levels"], r["served"])}
        if precision != "f32":
            low, ly, la, _ = qwen3.prefix_logits(w, config, toks, levels,
                                                 length, precision)
            got["control"] = (low, (ly, la),
                              [int(low[p].argmax()) for p in at])
        for side, (lg, lv, tokens) in got.items():
            ref, _, _, off = qwen3.prefix_logits(w, config, toks, levels,
                                                 length, levels=lv, tie=tie)
            sides[side]["gaps"].extend(qwen3.rel_gaps(lg, ref))
            sides[side]["misses"] += qwen3.greedy_misses(lg, tokens, at)
            sides[side]["not_taken"] += off
    for side, v in sides.items():
        print(f"serve: {side}: {len(v['gaps']) - 1} steps compared, widest "
              f"logit gaps {sorted(v['gaps'])[-3:]}, MLP levels off by "
              f"more than a tie {v['not_taken']}", file=sys.stderr)
    read = sides.get("control", sides["program"])
    prog = sides["program"]
    checks = [{"name": "logit_rel_gap", "value": max(read["gaps"]),
               "limit": config["check"]["logit_rel_gap"]},
              {"name": "served_not_greedy", "value": read["misses"],
               "limit": 0},
              {"name": "failed_requests", "limit": 0,
               "value": len(state.get("errors", ()))}]
    if precision != "f32":
        checks[0]["program"] = max(prog["gaps"])
        checks[1]["program"] = prog["misses"]
    return checks
