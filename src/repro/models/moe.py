"""Mixture-of-Experts with sort-based capacity dispatch.

Two parallelism modes (MoECfg.parallelism):

  "tp" (baseline): expert weights replicated over experts, FSDP-sharded on
      d_model over "data" and TP-sharded on d_ff over "model".  Dispatch is
      LOCAL to each data shard inside shard_map (the token sort never crosses
      chips); the expert matmuls all-gather their FSDP shards and psum the
      down-projection over "model" — Megatron-style MoE-TP.

  "ep" (hillclimb): experts sharded over "model" (E/tp local experts).
      Tokens all_to_all to their expert's owner shard, compute with whole
      local experts (no ff-dim psum), all_to_all back.  Collective payload is
      top_k * tokens * d_model instead of 2 * tokens * d_ff-activations worth
      of psum traffic — the collective-roofline lever for the MoE archs.

Both paths use the same local sort-based dispatch:
  router -> top-k -> flat (token, expert) pairs sorted by expert ->
  position-in-expert via rank-within-segment -> capacity-dropped scatter into
  an [E, C, d] buffer -> block-diagonal expert einsum -> weighted combine.

One chip's share (``MoECfg.held``): the layer holds only experts [first,
stop), routes over all of them, and returns the held experts' part of the
gated sum, on the AP bank (:func:`moe_ffn_ap`); it is served only there.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import MoECfg
from .common import MODEL_AXIS, act_fn, dense_init, mesh_data_axes


def init_moe(key, d_model: int, cfg: MoECfg, dtype=jnp.float32) -> dict:
    """Router over all experts; expert weights of the held ones."""
    k0, k1, k2, k3 = jax.random.split(key, 4)
    e, ff = cfg.n_held, cfg.d_ff
    return {
        "router": dense_init(k0, (d_model, cfg.n_experts), 0, jnp.float32),
        "w1": dense_init(k1, (e, d_model, ff), 1, dtype),
        "w3": dense_init(k2, (e, d_model, ff), 1, dtype),
        "w2": dense_init(k3, (e, ff, d_model), 1, dtype),
    }


def _route(x2d: jax.Array, router: jax.Array, cfg: MoECfg):
    """x2d [T, d] -> (gates [T, k] fp32, experts [T, k] int32)."""
    logits = x2d.astype(jnp.float32) @ router.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, experts.astype(jnp.int32)


def _dispatch_indices(experts: jax.Array, n_experts: int, capacity: int):
    """Sort-based dispatch bookkeeping.

    experts [T, k] -> (slot [T*k] target buffer slot or E*C if dropped,
    order info to map back).  Rank-within-expert computed on the sorted
    stream: pos_i = i - start_of_segment(expert_i).
    """
    t, k = experts.shape
    flat = experts.reshape(-1)                         # [T*k]
    perm = jnp.argsort(flat, stable=True)              # sorted by expert
    sorted_e = flat[perm]
    counts = jnp.bincount(flat, length=n_experts)      # [E]
    seg_start = jnp.cumsum(counts) - counts
    pos = jnp.arange(t * k) - seg_start[sorted_e]
    keep = pos < capacity
    slot_sorted = jnp.where(keep, sorted_e * capacity + pos,
                            n_experts * capacity)      # overflow -> dropped
    # slot for each original (token, k) pair
    slot = jnp.zeros((t * k,), jnp.int32).at[perm].set(
        slot_sorted.astype(jnp.int32))
    return slot


def _expert_ffn(buf: jax.Array, w1, w3, w2, act: str) -> jax.Array:
    """buf [E, C, d] -> [E, C, d_out_partial]."""
    h = jnp.einsum("ecd,edf->ecf", buf, w1)
    u = jnp.einsum("ecd,edf->ecf", buf, w3)
    h = act_fn(act)(h) * u
    return jnp.einsum("ecf,efd->ecd", h, w2)


def _local_moe_tp(x, router, w1, w3, w2, *, cfg: MoECfg, act: str,
                  fsdp_gather: bool):
    """Per-data-shard body (inside shard_map).  x [b_l, s, d] replicated over
    model; w1/w3 [E, d/dp, ff/tp], w2 [E, ff/tp, d/dp]."""
    if fsdp_gather:
        w1 = jax.lax.all_gather(w1, "data", axis=1, tiled=True)
        w3 = jax.lax.all_gather(w3, "data", axis=1, tiled=True)
        w2 = jax.lax.all_gather(w2, "data", axis=2, tiled=True)
    b, s, d = x.shape
    t = b * s
    x2d = x.reshape(t, d)
    gates, experts = _route(x2d, router, cfg)
    e = cfg.n_experts
    capacity = max(8, int(t * cfg.top_k * cfg.capacity_factor / e))
    slot = _dispatch_indices(experts, e, capacity)
    # scatter tokens (duplicated per k) into the capacity buffer
    buf = jnp.zeros((e * capacity + 1, d), x.dtype)
    xk = jnp.repeat(x2d, cfg.top_k, axis=0)            # [T*k, d]
    buf = buf.at[slot].set(xk, mode="drop")
    out_buf = _expert_ffn(buf[:-1].reshape(e, capacity, d),
                          w1, w3, w2, act)             # partial over tp
    out_buf = jax.lax.psum(out_buf, MODEL_AXIS)
    out_flat = out_buf.reshape(e * capacity, d)
    out_flat = jnp.concatenate(
        [out_flat, jnp.zeros((1, d), out_flat.dtype)], 0)
    yk = out_flat[slot]                                # [T*k, d], 0 if dropped
    yk = yk * gates.reshape(-1, 1).astype(yk.dtype)
    y2d = yk.reshape(t, cfg.top_k, d).sum(axis=1)
    return y2d.reshape(b, s, d)


def _local_moe_ep(x, router, w1, w3, w2, *, cfg: MoECfg, act: str,
                  tp_size: int):
    """Expert-parallel body: experts sharded over "model" (E_l = E/tp).

    x enters replicated over "model" (it is sharded over the data axes
    only), so the tokens are first SPLIT across the model axis — each model
    shard dispatches its own 1/tp slice (without this, every expert receives
    each token tp times and compute blows up tp-fold; measured in the first
    EP §Perf iteration).  Then: local sort-based dispatch, all_to_all over
    "model", whole-expert FFN, all_to_all back, combine, and a final
    all_gather restores model-replication of the output.
    """
    b, s, d = x.shape
    t_full = b * s
    x2d_full = x.reshape(t_full, d)
    my = jax.lax.axis_index(MODEL_AXIS)
    t = t_full // tp_size                              # tokens per model shard
    x2d = jax.lax.dynamic_slice_in_dim(x2d_full, my * t, t, axis=0)
    gates, experts = _route(x2d, router, cfg)
    e = cfg.n_experts
    e_local = e // tp_size
    # capacity per (destination shard, local expert) buffer
    capacity = max(8, int(t * cfg.top_k * cfg.capacity_factor / e))
    slot = _dispatch_indices(experts, e, capacity)     # global-expert slots
    buf = jnp.zeros((e * capacity + 1, d), x.dtype)
    xk = jnp.repeat(x2d, cfg.top_k, axis=0)
    buf = buf.at[slot].set(xk, mode="drop")
    send = buf[:-1].reshape(tp_size, e_local * capacity, d)
    recv = jax.lax.all_to_all(send, MODEL_AXIS, split_axis=0, concat_axis=0,
                              tiled=False)             # [tp, E_l*C, d]
    recv = recv.reshape(tp_size, e_local, capacity, d) \
        .transpose(1, 0, 2, 3).reshape(e_local, tp_size * capacity, d)
    out = _expert_ffn(recv, w1, w3, w2, act)           # whole local experts
    out = out.reshape(e_local, tp_size, capacity, d) \
        .transpose(1, 0, 2, 3).reshape(tp_size, e_local * capacity, d)
    back = jax.lax.all_to_all(out, MODEL_AXIS, split_axis=0, concat_axis=0,
                              tiled=False)
    out_flat = back.reshape(e * capacity, d)
    out_flat = jnp.concatenate(
        [out_flat, jnp.zeros((1, d), out_flat.dtype)], 0)
    yk = out_flat[slot] * gates.reshape(-1, 1).astype(x.dtype)
    y2d = yk.reshape(t, cfg.top_k, d).sum(axis=1)      # [t, d] (my slice)
    y_full = jax.lax.all_gather(y2d, MODEL_AXIS, axis=0, tiled=True)
    return y_full.reshape(b, s, d)


def expert_key(layer: int | None, name: str) -> str:
    """The AP serving context's key (and resident label) of a MoE layer's
    projection ``name``."""
    return f"moe.{name}" if layer is None else f"moe.L{layer}.{name}"


EXPERT_LEAVES = ("w1", "w3", "w2")


def held_capacity(cfg: MoECfg, n_tokens: int) -> int:
    """The (token, expert) pairs the held experts take at capacity:
    ``capacity_factor`` times their share of ``n_tokens`` x top_k pairs
    under even routing."""
    return math.ceil(cfg.capacity_factor * n_tokens * cfg.top_k
                     * cfg.n_held / cfg.n_experts)


def prepare_moe_ap(ctx, layer: int | None, p: dict) -> None:
    """Convert one MoE layer's held experts for the AP bank, once, from the
    weights given (ternary, absmean per output channel, in float32); a
    projection whose weights ``p`` leaves out must be converted already."""
    for name in EXPERT_LEAVES:
        ctx.expert_stack(expert_key(layer, name), p.get(name))


def moe_ffn_ap(p: dict, x: jax.Array, cfg: MoECfg, act: str,
               ctx) -> jax.Array:
    """AP-served MoE: the router runs in float over all experts (top-k,
    renormalised), then the held experts' SwiGLU projections go through
    :func:`repro.apc.layers.ap_moe_dispatch` as grouped MACs — one per
    projection over every held (token, expert) pair, launching at least
    the held experts' capacity (:func:`held_capacity`).  The expert stacks
    are the context's, keyed by the layer being served
    (:func:`~repro.apc.layers.current_ap_layer`) and converted once
    (:func:`prepare_moe_ap`, or here on first use)."""
    from ..apc import trace
    from ..apc.layers import ap_moe_dispatch, current_ap_layer
    b, s, d = x.shape
    x2d = x.reshape(b * s, d)
    with trace.annotate("ap.moe.route"):
        gates, experts = _route(x2d, p["router"], cfg)
    layer = current_ap_layer()
    w1, w3, w2 = (ctx.expert_stack(expert_key(layer, n), p.get(n))
                  for n in EXPERT_LEAVES)
    y2d = ap_moe_dispatch(ctx, x2d, experts, gates, w1, w3, w2,
                          act_fn(act), first=cfg.held_range[0],
                          capacity=held_capacity(cfg, b * s), layer=layer)
    return y2d.reshape(b, s, -1).astype(x.dtype)


def moe_ffn(p: dict, x: jax.Array, cfg: MoECfg, act: str,
            mesh: jax.sharding.Mesh) -> jax.Array:
    """Public MoE entry: wraps the local body in shard_map on `mesh`."""
    from ..apc.layers import current_ap_context
    ctx = current_ap_context()
    if ctx is not None:                      # AP-backed serving path
        return moe_ffn_ap(p, x, cfg, act, ctx)
    if cfg.held is not None:
        raise ValueError(f"experts {cfg.held} of {cfg.n_experts} are one "
                         f"chip's share, served only on the AP bank "
                         f"(an ap_serving context)")
    tp_size = mesh.shape[MODEL_AXIS]
    da = mesh_data_axes(mesh)
    dp = 1
    for a in da:
        dp *= mesh.shape[a]
    if x.shape[0] % dp != 0:
        da = None      # decode batch=1 etc.: replicate over the data axes
    t_total = x.shape[0] * x.shape[1]
    if cfg.parallelism == "ep" and cfg.n_experts % tp_size == 0 \
            and tp_size > 1 and t_total % tp_size == 0:
        body = functools.partial(_local_moe_ep, cfg=cfg, act=act,
                                 tp_size=tp_size)
        in_specs = (P(da, None, None),                 # x
                    P(None, None),                     # router (replicated)
                    P(MODEL_AXIS, None, None),         # w1 [E/tp, d, ff]
                    P(MODEL_AXIS, None, None),         # w3
                    P(MODEL_AXIS, None, None))         # w2 [E/tp, ff, d]
    else:
        fsdp = mesh.shape["data"] > 1
        body = functools.partial(_local_moe_tp, cfg=cfg, act=act,
                                 fsdp_gather=fsdp)
        in_specs = (P(da, None, None),
                    P(None, None),
                    P(None, "data", MODEL_AXIS),
                    P(None, "data", MODEL_AXIS),
                    P(None, MODEL_AXIS, "data"))
    fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=P(da, None, None), check_vma=False)
    return fn(x, p["router"], p["w1"], p["w3"], p["w2"])
