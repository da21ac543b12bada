"""Dense SwiGLU MLP + the ternary-quantized linear path (paper technique).

The ternary path (TernaryCfg.enabled / qat) implements DESIGN.md §2:
balanced-ternary weights with per-channel absmean scale.  During training the
straight-through estimator keeps full-precision master weights; at serve time
weights are packed 16-per-int32 (kernels/ternary_matmul) — here the jnp
fake-quant form is used so the whole model stays lowerable on any backend,
with the Pallas kernel validated separately as the TPU execution path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.ternary_matmul.ref import quantize_ternary
from .common import act_fn, dense_init
from .quant import MLP_KEYS


def ternary_linear(x: jax.Array, w: jax.Array, qat: bool) -> jax.Array:
    """y = x @ ternarize(w), STE in training (qat) or fake-quant inference."""
    w_ter, scale = quantize_ternary(w.astype(jnp.float32))
    w_q = (w_ter.astype(jnp.float32) * scale[None, :]).astype(w.dtype)
    if qat:
        # straight-through: forward w_q, gradient flows to w
        w_q = w + jax.lax.stop_gradient(w_q - w)
    return x @ w_q


def linear(x: jax.Array, w: jax.Array, ternary: bool = False,
           qat: bool = False) -> jax.Array:
    if ternary:
        return ternary_linear(x, w, qat)
    return x @ w


def init_mlp(key, d_model: int, d_ff: int, dtype=jnp.float32) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w1": dense_init(k1, (d_model, d_ff), 0, dtype),   # gate
        "w3": dense_init(k2, (d_model, d_ff), 0, dtype),   # up
        "w2": dense_init(k3, (d_ff, d_model), 0, dtype),   # down
    }


def mlp_key(layer: int | None, name: str) -> str:
    """The AP serving context's key (and resident label) of a dense MLP
    layer's projection ``name``."""
    return f"mlp.{name}" if layer is None else f"mlp.L{layer}.{name}"


def _packed_stack(p: dict, name: str):
    """``make`` for :meth:`~repro.apc.layers.APServeContext.stack`: the
    served packed weights of projection ``name`` as a stack of one."""
    from ..apc.layers import APExpertStack
    return functools.partial(APExpertStack.from_packed, p[f"{name}_packed"],
                             p[f"{name}_scale"])


def prepare_mlp_ap(ctx, layer: int | None, p: dict) -> None:
    """Convert one dense MLP layer's packed projections for the AP bank,
    once: each a stack of one projection, keyed by layer and projection,
    its digits and scale those served (``p`` as the layer's step sees
    it)."""
    for name in MLP_KEYS:
        ctx.stack(mlp_key(layer, name), _packed_stack(p, name))


def mlp_ap(p: dict, x: jax.Array, act: str, ctx) -> jax.Array:
    """AP-served SwiGLU on packed ternary weights: gate and up projections
    are INDEPENDENT tiled-MAC subgraphs of one ProgramGraph (the runtime
    interleaves their tiles across the array bank); the down projection
    runs in a second graph after the float combine.  Each projection is
    the context's stack of one, keyed by the layer being served
    (:func:`~repro.apc.layers.current_ap_layer`) and converted once
    (:func:`prepare_mlp_ap`, or here on first use), so a tile node's build
    makes every tile's rows in one call.  Activations quantize to
    ``ctx.x_levels`` integers per projection — the AP arithmetic on the
    quantized grid is exact, and every compare/write cycle lands in
    ``ctx.stats`` for the per-request Table XI report."""
    from ..apc import trace
    from ..apc.graph import ProgramGraph
    from ..apc.layers import current_ap_layer
    lead, d = x.shape[:-1], x.shape[-1]
    x2d = x.reshape(-1, d)
    layer = current_ap_layer()
    rows = np.zeros(x2d.shape[0], np.int32)   # every row on projection 0

    def add(graph, w, q):
        if q.shape[1] < w.k:              # pack-time padding rows: w == 0
            q = jnp.pad(q, ((0, 0), (0, w.k - q.shape[1])))
        return w.add_call(graph, q, rows, max_cols=ctx.max_cols,
                          max_q=ctx.x_levels)

    with trace.annotate("ap.model.graph_build"):
        w1, w3, w2 = (ctx.stack(mlp_key(layer, n), _packed_stack(p, n))
                      for n in MLP_KEYS)
        x_int, s_x = ctx.quantize(x2d)
        g1 = ProgramGraph()
        c1, c3 = add(g1, w1, x_int), add(g1, w3, x_int)
    res1 = ctx.run_graph(g1)
    h = act_fn(act)(c1.decode(res1, s_x)) * c3.decode(res1, s_x)
    with trace.annotate("ap.model.graph_build"):
        h_int, s_h = ctx.quantize(h)
        g2 = ProgramGraph()
        c2 = add(g2, w2, h_int)
    res2 = ctx.run_graph(g2)
    y = c2.decode(res2, s_h)
    return y.reshape(*lead, y.shape[-1]).astype(x.dtype)


def mlp(p: dict, x: jax.Array, act: str = "silu", ternary: bool = False,
        qat: bool = False) -> jax.Array:
    if "w1_packed" in p:                     # packed ternary serving weights
        from ..apc.layers import current_ap_context
        ctx = current_ap_context()
        if ctx is not None:                  # AP-backed serving path
            return mlp_ap(p, x, act, ctx)
        from .quant import unpack_matmul
        h = act_fn(act)(unpack_matmul(x, p["w1_packed"], p["w1_scale"])) \
            * unpack_matmul(x, p["w3_packed"], p["w3_scale"])
        return unpack_matmul(h, p["w2_packed"], p["w2_scale"])
    h = act_fn(act)(linear(x, p["w1"], ternary, qat)) \
        * linear(x, p["w3"], ternary, qat)
    return linear(h, p["w2"], ternary, qat)
