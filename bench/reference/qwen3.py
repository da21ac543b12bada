"""Plain float32 reference of the served Qwen3 decoder, and its weights.

Written from the Qwen3 architecture (pre-norm decoder layer: RMSNorm, GQA
attention with per-head RMSNorm on q and k and half-split rotary position
embedding, SwiGLU MLP; final RMSNorm; output head tied to the embedding),
independent of the program under test.  The MLP is the served one: its
weights are ternarised per output channel by the absmean rule
(``w_ter = clip(round(w / mean|w|), -1, 1)``, scale ``mean|w|``), and its
inputs are quantised per token to integers ``|x| <= x_levels`` with scale
``max|x| / x_levels``; the integer products are exact in float32.

``weights`` makes the configuration's weights from a seed, in float32, in
one jitted call; the benchmark hands the same weights to the program.
``forward`` runs a whole sequence at once (causal attention), at float32
and ``Precision.HIGHEST``.  The MLP's rounding to integer levels turns a
difference in the last bits of its input into a whole level now and then;
``forward`` can take another computation's level at such a tie (an input
within ``tie`` of a rounding boundary), so that a comparison of logits sees
the precision of the rest and not which way a tie fell.
``precision="fp8"`` rounds the operands of every
float matmul (projections, attention scores and values, the head) to
float8 (e4m3) first, the control that the check must reject.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
NOT_GIVEN = 99                  # a level no computation gives: never taken
LEAVES = ("norm1", "norm2", "wq", "wk", "wv", "wo", "q_norm", "k_norm",
          "w1", "w3", "w2")


def dims(config: dict) -> dict:
    return {"d": int(config["hidden_size"]), "ff": int(config["intermediate_size"]),
            "h": int(config["num_attention_heads"]),
            "hk": int(config["num_key_value_heads"]),
            "hd": int(config["head_dim"]), "v": int(config["vocab_size"]),
            "layers": int(config["num_hidden_layers"]),
            "theta": float(config["rope_theta"]),
            "eps": float(config["rms_norm_eps"])}


def seed_key(seed: int):
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, seed >> 32)


def weights(config: dict, seed: int) -> dict:
    """``{"embed", "final_norm", "layers": [{leaf: array}]}`` in float32:
    embedding N(0, 0.02^2), projections N(0, 1/fan_in), norm gains
    1 + N(0, 0.1^2); every leaf from its own fold of the seed's key."""
    m = dims(config)
    d, ff, h, hk, hd = m["d"], m["ff"], m["h"], m["hk"], m["hd"]
    shapes = {"norm1": (d,), "norm2": (d,), "wq": (d, h * hd),
              "wk": (d, hk * hd), "wv": (d, hk * hd), "wo": (h * hd, d),
              "q_norm": (hd,), "k_norm": (hd,), "w1": (d, ff), "w3": (d, ff),
              "w2": (ff, d)}

    def leaf(key, name):
        shape = shapes[name]
        z = jax.random.normal(key, shape, jnp.float32)
        if len(shape) == 1:
            return 1.0 + 0.1 * z
        return z / jnp.sqrt(jnp.float32(shape[0]))

    @jax.jit
    def make(key):
        ks = jax.random.split(key, 2 + m["layers"])
        out = {"embed": 0.02 * jax.random.normal(ks[0], (m["v"], d),
                                                 jnp.float32),
               "final_norm": 1.0 + 0.1 * jax.random.normal(ks[1], (d,),
                                                           jnp.float32)}
        out["layers"] = [
            {n: leaf(jax.random.fold_in(ks[2 + i], j), n)
             for j, n in enumerate(LEAVES)} for i in range(m["layers"])]
        return out

    return make(seed_key(seed))


def ternarize(w):
    scale = jnp.maximum(jnp.mean(jnp.abs(w), axis=0), 1e-8)
    return jnp.clip(jnp.round(w / scale), -1, 1), scale


def quantize_rows(x, levels: int):
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True) / levels,
                    1e-8)
    return jnp.clip(jnp.round(x / s), -levels, levels), s


def rms_norm(x, g, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rope(x, theta: float):
    """x [S, heads, hd], position = row index."""
    s, hd = x.shape[0], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def logits(w: dict, config: dict, tokens, x_levels: int,
           precision: str = "f32"):
    """Logits [S, vocab] of ``tokens`` [S], every position at once."""
    return forward(w, config, tokens, x_levels, precision)[0]


def forward(w: dict, config: dict, tokens, x_levels: int,
            precision: str = "f32", levels=None, tie: float = 0.0):
    """``(logits [S, vocab], y levels [L, S, d], a levels [L, S, ff],
    levels given but not taken)``.

    The MLP input levels are the reference's own, except where ``levels``
    (the ``(y, a)`` levels another computation chose, same shapes) differ
    by one step at an input whose value lies within ``tie`` (relative to
    its size or its row's rms) of the rounding boundary between the two: a
    tie within the serving precision, which may round either way.  There the
    given level is taken, and the rest follows from it."""
    m = dims(config)
    s = len(tokens)
    if levels is None:
        levels = (jnp.full((m["layers"], s, m["d"]), NOT_GIVEN, jnp.int32),
                  jnp.full((m["layers"], s, m["ff"]), NOT_GIVEN, jnp.int32))
    return _forward(w, tuple(sorted(m.items())), tokens, x_levels, precision,
                    levels[0], levels[1], jnp.float32(tie))


def take_ties(v, own, given, tie):
    """The given level where it is one step from the reference's own and
    the value ``v`` (rows of level units) lies within ``tie`` of the
    boundary between them, relative to its size or to its row's rms,
    whichever is larger (rounding in a sum reaches small entries from the
    large ones)."""
    boundary = (own + given) / 2.0
    size = jnp.maximum(jnp.abs(v),
                       jnp.sqrt(jnp.mean(v * v, axis=-1, keepdims=True)))
    near = jnp.abs(v - boundary) <= tie * size
    take = (jnp.abs(given - own) == 1) & near
    return (jnp.where(take, given, own),
            jnp.sum((given != own) & ~take & (given != NOT_GIVEN)))


@functools.partial(jax.jit, static_argnums=(1, 3, 4))
def _forward(w, m, tokens, x_levels, precision, given_y, given_a, tie):
    m = dict(m)
    h, hk, hd, eps = m["h"], m["hk"], m["hd"], m["eps"]

    def low(a):
        if precision == "fp8":
            return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        return a

    def mm(a, b):
        return jnp.matmul(low(a), low(b), precision=HIGHEST)

    def exact(a, b):                  # integer products of the AP MLP
        return jnp.matmul(a, b, precision=HIGHEST)

    x = w["embed"][tokens]
    s = x.shape[0]
    causal = jnp.tril(jnp.ones((s, s), bool))
    ys, as_, not_taken = [], [], 0
    for i, p in enumerate(w["layers"]):
        y = rms_norm(x, p["norm1"], eps)
        q = rms_norm(mm(y, p["wq"]).reshape(s, h, hd), p["q_norm"], eps)
        k = rms_norm(mm(y, p["wk"]).reshape(s, hk, hd), p["k_norm"], eps)
        v = mm(y, p["wv"]).reshape(s, hk, hd)
        q, k = rope(q, m["theta"]), rope(k, m["theta"])
        kv = jnp.arange(h) // (h // hk)
        sc = jnp.einsum("qhd,khd->hqk", low(q), low(k[:, kv]),
                        precision=HIGHEST)
        sc = jnp.where(causal, sc / jnp.sqrt(jnp.float32(hd)), -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", low(jax.nn.softmax(sc, axis=-1)),
                       low(v[:, kv]), precision=HIGHEST)
        x = x + mm(o.reshape(s, h * hd), p["wo"])
        y = rms_norm(x, p["norm2"], eps)
        yi, sy = quantize_rows(y, x_levels)
        yi, off = take_ties(y / sy, yi, given_y[i], tie)
        w1, s1 = ternarize(p["w1"])
        w3, s3 = ternarize(p["w3"])
        w2, s2 = ternarize(p["w2"])
        g = exact(yi, w1) * sy * s1
        u = exact(yi, w3) * sy * s3
        a = jax.nn.silu(g) * u
        ai, sa = quantize_rows(a, x_levels)
        ai, off_a = take_ties(a / sa, ai, given_a[i], tie)
        x = x + exact(ai, w2) * sa * s2
        ys.append(yi)
        as_.append(ai)
        not_taken = not_taken + off + off_a
    x = rms_norm(x, w["final_norm"], eps)
    return (mm(x, w["embed"].T), jnp.stack(ys).astype(jnp.int32),
            jnp.stack(as_).astype(jnp.int32), not_taken)


def prefix_logits(w: dict, config: dict, tokens, x_levels: int,
                  length: int, precision: str = "f32", levels=None,
                  tie: float = 0.0):
    """``forward`` over the first n = len(tokens) positions: logits [n,
    vocab] (float64, on the host), y and a levels [L, n, .], levels given
    but not taken.  The sequence is padded at its end to ``length``, so
    every request compiles one program; causal attention leaves the first
    n positions as they would be alone, and padded positions take no
    given level."""
    import numpy as np
    tokens = np.asarray(tokens, np.int32)
    n = len(tokens)
    padded = np.zeros(length, np.int32)
    padded[:n] = tokens
    if levels is not None:
        levels = tuple(np.pad(np.asarray(lv, np.int32),
                              ((0, 0), (0, length - n), (0, 0)),
                              constant_values=NOT_GIVEN) for lv in levels)
    out, ys, as_, off = forward(w, config, jnp.asarray(padded), x_levels,
                                precision, levels, tie)
    return (np.asarray(out, np.float64)[:n], np.asarray(ys)[:, :n],
            np.asarray(as_)[:, :n], int(off))


def rel_gaps(got, ref):
    """Per position: the distance between two rows of logits over the
    reference's norm, ``|got - ref| / |ref|``."""
    import numpy as np
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return list(np.linalg.norm(got - ref, axis=-1)
                / np.linalg.norm(ref, axis=-1))


def greedy_misses(logits, served, at) -> int:
    """Served tokens that are not a greedy pick: ``served[j]``, produced at
    position ``at[j]``, has a logit below that position's best."""
    import numpy as np
    logits = np.asarray(logits)
    return int(sum(logits[p, t] < logits[p].max() for t, p in zip(served, at)))
