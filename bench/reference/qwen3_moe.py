"""Plain float32 reference of the served Qwen3-MoE decoder, and its weights.

Written from the Qwen3-MoE architecture (``Qwen3MoeForCausalLM``), independent
of the program under test: pre-norm decoder layers of GQA attention (per-head
RMSNorm on q and k, half-split rotary embedding; ``qwen3.py``'s norm and
rotary) and a sparse-expert FFN, final RMSNorm, untied head.  The FFN of a
token ``u`` (the normed residual):

    p = softmax(u @ W_router)            over all router_width experts
    S = top-k(p),  g_e = p_e / sum_{j in S} p_j        (norm_topk_prob)
    y = sum_{e in S, e held} g_e * W2_e(silu(W1_e u) * W3_e u)

on one chip's share of the experts (``held_experts``, ``[first, stop)``):
the others are left out, as the chip serving the share leaves them out.
The experts are the served ones: ternarised per output channel by the absmean
rule (``qwen3.ternarize``); ``u`` is quantised per token and the hidden ``h``
per (token, expert) row to integers ``|q| <= x_levels``
(``qwen3.quantize_rows``), so the integer products are exact in float32.

``weights`` makes the configuration's weights from a seed, in float32, in one
jitted call; each expert from its own fold of its layer's key, so a share's
experts are the same whichever share is made (``experts=``).  ``forward``
runs a whole sequence at once, at float32 and ``Precision.HIGHEST``.  Two
kinds of tie, where the serving precision may fall either way, take another
computation's choice (``given``): a level within ``tie`` of a rounding
boundary (``qwen3.take_ties``), and a route where the router's k-th and
(k+1)-th probabilities lie within ``route_tie`` of each other, relative to
the k-th.  Routes given and differing outside the margin are counted.
Every matmul runs under ``jax.default_matmul_precision("highest")`` (and names
``HIGHEST``).  ``precision="fp8"`` rounds the operands of every float matmul (projections,
router, attention scores and values, the head) to float8 e4m3 first, the
control that the check must reject.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from reference import qwen3

HIGHEST = qwen3.HIGHEST
NOT_GIVEN = qwen3.NOT_GIVEN
ATTN = ("norm1", "norm2", "wq", "wk", "wv", "wo", "q_norm", "k_norm",
        "router")


def dims(config: dict) -> dict:
    first, stop = (int(v) for v in config["held_experts"])
    return {"d": int(config["hidden_size"]),
            "ff": int(config["moe_intermediate_size"]),
            "h": int(config["num_attention_heads"]),
            "hk": int(config["num_key_value_heads"]),
            "hd": int(config["head_dim"]), "v": int(config["vocab_size"]),
            "layers": int(config["num_hidden_layers"]),
            "theta": float(config["rope_theta"]),
            "eps": float(config["rms_norm_eps"]),
            "router": int(config["router_width"]),
            "k": int(config["num_experts_per_tok"]),
            "norm_topk": bool(config["norm_topk_prob"]),
            "first": first, "held": stop - first}


def make_weights(config: dict, experts: tuple[int, int] | None = None, *,
                 with_experts: bool = True):
    """``key -> weights`` (jittable): ``{"embed", "head", "final_norm",
    "layers": [{leaf: array}]}`` in float32; each layer's ``w1``/``w3``
    [E, d, ff] and ``w2`` [E, ff, d] hold experts ``experts`` (default the
    configuration's share), or are left out (``with_experts=False``; see
    ``make_experts``).  Embedding N(0, 0.02^2), projections and router
    N(0, 1/fan_in), norm gains 1 + N(0, 0.1^2)."""
    m = tuple(sorted(dims(config).items()))
    return _make_weights(m, experts, with_experts)


def make_experts(config: dict, experts: tuple[int, int] | None = None):
    """``(key, layer) -> {"w1", "w3", "w2"}`` (jittable, ``layer`` may be
    traced): the expert weights of one layer, as ``make_weights`` makes
    them from the same key."""
    return _make_experts(tuple(sorted(dims(config).items())), experts)


def weights(config: dict, seed: int,
            experts: tuple[int, int] | None = None) -> dict:
    return _jitted_weights(tuple(sorted(dims(config).items())), experts)(
        qwen3.seed_key(seed))


@functools.lru_cache(maxsize=8)
def _jitted_weights(m: tuple, experts):
    return jax.jit(_make_weights(m, experts, True))


def _leaf(key, shape):
    z = jax.random.normal(key, shape, jnp.float32)
    if len(shape) == 1:
        return 1.0 + 0.1 * z
    return z / jnp.sqrt(jnp.float32(shape[0]))


def _layer_keys(m: dict, key):
    """The keys of the embedding, final norm, head and each layer."""
    return jax.random.split(key, 3 + m["layers"])


def _make_experts(m: tuple, experts):
    m = dict(m)
    d, ff = m["d"], m["ff"]
    first, stop = experts or (m["first"], m["first"] + m["held"])

    def expert(layer_key, e):
        k1, k3, k2 = jax.random.split(jax.random.fold_in(layer_key, 1000 + e),
                                      3)
        return _leaf(k1, (d, ff)), _leaf(k3, (d, ff)), _leaf(k2, (ff, d))

    def make(key, layer):
        lk = _layer_keys(m, key)[3 + layer]
        w1, w3, w2 = jax.vmap(lambda e: expert(lk, e))(
            jnp.arange(first, stop))
        return {"w1": w1, "w3": w3, "w2": w2}

    return make


def _make_weights(m: tuple, experts, with_experts: bool):
    layer_experts = _make_experts(m, experts)
    m = dict(m)
    d, h, hk, hd = m["d"], m["h"], m["hk"], m["hd"]
    shapes = {"norm1": (d,), "norm2": (d,), "wq": (d, h * hd),
              "wk": (d, hk * hd), "wv": (d, hk * hd), "wo": (h * hd, d),
              "q_norm": (hd,), "k_norm": (hd,), "router": (d, m["router"])}

    def make(key):
        ks = _layer_keys(m, key)
        out = {"embed": 0.02 * jax.random.normal(ks[0], (m["v"], d),
                                                 jnp.float32),
               "final_norm": _leaf(ks[1], (d,)),
               "head": _leaf(ks[2], (d, m["v"])), "layers": []}
        for i in range(m["layers"]):
            lk = ks[3 + i]
            p = {n: _leaf(jax.random.fold_in(lk, j), shapes[n])
                 for j, n in enumerate(ATTN)}
            if with_experts:
                p.update(layer_experts(key, i))
            out["layers"].append(p)
        return out

    return make


def not_given(config: dict, s: int) -> tuple:
    """``given`` of a sequence of ``s`` positions that gives nothing:
    (x levels [L, s, d], h levels [L, s, E, ff], routes [L, s, k])."""
    m = dims(config)
    return (np.full((m["layers"], s, m["d"]), NOT_GIVEN, np.int32),
            np.full((m["layers"], s, m["held"], m["ff"]), NOT_GIVEN,
                    np.int32),
            np.full((m["layers"], s, m["k"]), NOT_GIVEN, np.int32))


def forward(w: dict, config: dict, tokens, x_levels: int,
            precision: str = "f32", given=None, tie: float = 0.0,
            route_tie: float = 0.0):
    """``(logits [S, vocab], (x levels, h levels, routes) as in
    ``not_given``, counts)`` of ``tokens`` [S], every position at once.

    ``counts``: levels given but not taken (``not_taken``), routes taken
    at a tie (``routes_taken``) and routes given that differ outside the
    margin (``routes_off``)."""
    m = dims(config)
    given = not_given(config, len(tokens)) if given is None else given
    with jax.default_matmul_precision("highest"):
        out = _forward(w, tuple(sorted(m.items())), tokens, x_levels,
                       precision, *(jnp.asarray(g) for g in given),
                       jnp.float32(tie), jnp.float32(route_tie))
    logits, levels, counts = out[0], out[1:4], out[4:]
    return logits, levels, dict(zip(("not_taken", "routes_taken",
                                     "routes_off"),
                                    (int(c) for c in counts)))


def route(probs, k: int, given, route_tie):
    """The top-k experts [S, k] of ``probs`` [S, E]; at a tie (k-th and
    (k+1)-th within ``route_tie`` of the k-th) the given route where one is
    given.  Returns ``(route, taken, off)``."""
    top_p, top_i = jax.lax.top_k(probs, k + 1)
    own = top_i[:, :k]
    near = top_p[:, k - 1] - top_p[:, k] <= route_tie * top_p[:, k - 1]
    have = given[:, 0] != NOT_GIVEN
    same = jnp.all(jnp.sort(given, axis=-1) == jnp.sort(own, axis=-1),
                   axis=-1)
    take = have & near & ~same
    return (jnp.where(take[:, None], given, own), jnp.sum(take),
            jnp.sum(have & ~near & ~same))


def moe(p: dict, u, m: dict, x_levels: int, mm, given_x, given_h,
        given_r, tie, route_tie):
    """One layer's sparse-expert FFN of the normed residual ``u`` [S, d]
    over the experts ``p`` holds (``m["first"]``, ``m["held"]``):
    ``(y [S, d], (x levels [S, d], h levels [S, E, ff], route [S, k]),
    (levels not taken, routes taken, routes off))``; ``mm`` is the float
    matmul (the router's)."""
    probs = jax.nn.softmax(mm(u, p["router"]), axis=-1)
    r, taken, off = route(probs, m["k"], given_r, route_tie)
    chosen = jnp.sum(jax.nn.one_hot(r, probs.shape[-1]), axis=1)
    g = probs * chosen
    if m["norm_topk"]:
        g = g / jnp.sum(g, axis=-1, keepdims=True)
    g = g[:, m["first"]:m["first"] + m["held"]]             # [S, E]
    xi, sx = qwen3.quantize_rows(u, x_levels)
    xi, off_x = qwen3.take_ties(u / sx, xi, given_x, tie)
    w1, s1 = jax.vmap(qwen3.ternarize)(p["w1"])             # [E, d, ff]
    w3, s3 = jax.vmap(qwen3.ternarize)(p["w3"])
    w2, s2 = jax.vmap(qwen3.ternarize)(p["w2"])
    gate = jnp.einsum("sd,edf->esf", xi, w1, precision=HIGHEST) \
        * sx[None] * s1[:, None]
    up = jnp.einsum("sd,edf->esf", xi, w3, precision=HIGHEST) \
        * sx[None] * s3[:, None]
    hh = jax.nn.silu(gate) * up                             # [E, S, ff]
    hi, sh = qwen3.quantize_rows(hh, x_levels)
    hi, off_h = qwen3.take_ties(hh / sh, hi, jnp.swapaxes(given_h, 0, 1),
                                tie)
    ye = jnp.einsum("esf,efd->esd", hi, w2, precision=HIGHEST) \
        * sh * s2[:, None]
    y = jnp.einsum("se,esd->sd", g, ye, precision=HIGHEST)
    return y, (xi, jnp.swapaxes(hi, 0, 1), r), (off_x + off_h, taken, off)


@functools.partial(jax.jit, static_argnums=(1, 3, 4))
def _forward(w, m, tokens, x_levels, precision, given_x, given_h, given_r,
             tie, route_tie):
    m = dict(m)
    h, hk, hd, eps = m["h"], m["hk"], m["hd"], m["eps"]

    def low(a):
        if precision == "fp8":
            return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        return a

    def mm(a, b):
        return jnp.matmul(low(a), low(b), precision=HIGHEST)

    x = w["embed"][tokens]
    s = x.shape[0]
    causal = jnp.tril(jnp.ones((s, s), bool))
    xs, hs, rs = [], [], []
    not_taken = taken = off = 0
    for i, p in enumerate(w["layers"]):
        y = qwen3.rms_norm(x, p["norm1"], eps)
        q = qwen3.rms_norm(mm(y, p["wq"]).reshape(s, h, hd), p["q_norm"], eps)
        kk = qwen3.rms_norm(mm(y, p["wk"]).reshape(s, hk, hd), p["k_norm"],
                            eps)
        v = mm(y, p["wv"]).reshape(s, hk, hd)
        q, kk = qwen3.rope(q, m["theta"]), qwen3.rope(kk, m["theta"])
        kv = jnp.arange(h) // (h // hk)
        sc = jnp.einsum("qhd,khd->hqk", low(q), low(kk[:, kv]),
                        precision=HIGHEST)
        sc = jnp.where(causal, sc / jnp.sqrt(jnp.float32(hd)), -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", low(jax.nn.softmax(sc, axis=-1)),
                       low(v[:, kv]), precision=HIGHEST)
        x = x + mm(o.reshape(s, h * hd), p["wo"])

        u = qwen3.rms_norm(x, p["norm2"], eps)
        y, (xi, hi, r), (n_off, t_, o_) = moe(
            p, u, m, x_levels, mm, given_x[i], given_h[i], given_r[i], tie,
            route_tie)
        x = x + y
        xs.append(xi)
        hs.append(hi)
        rs.append(r)
        not_taken = not_taken + n_off
        taken, off = taken + t_, off + o_
    x = qwen3.rms_norm(x, w["final_norm"], eps)
    return (mm(x, w["head"]), jnp.stack(xs).astype(jnp.int32),
            jnp.stack(hs).astype(jnp.int32), jnp.stack(rs).astype(jnp.int32),
            not_taken, taken, off)


def prefix_logits(w: dict, config: dict, tokens, x_levels: int,
                  length: int, precision: str = "f32", given=None,
                  tie: float = 0.0, route_tie: float = 0.0):
    """``forward`` over the first n = len(tokens) positions: logits [n,
    vocab] (float64, on the host), the levels and routes [L, n, ...] and
    the counts.  The sequence is padded at its end to ``length``, so every
    request compiles one program; causal attention leaves the first n
    positions as they would be alone, and padded positions take nothing
    given."""
    tokens = np.asarray(tokens, np.int32)
    n = len(tokens)
    padded = np.zeros(length, np.int32)
    padded[:n] = tokens
    full = not_given(config, length)
    if given is not None:
        for f, g in zip(full, given):
            f[:, :n] = g
    logits, levels, counts = forward(w, config, jnp.asarray(padded),
                                     x_levels, precision, full, tie,
                                     route_tie)
    return (np.asarray(logits, np.float64)[:n],
            tuple(np.asarray(lv)[:, :n] for lv in levels), counts)
