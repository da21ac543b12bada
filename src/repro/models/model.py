"""Top-level LM: embedding -> scanned layer stack -> head, + serve paths.

Compile-friendliness: the layer stack is a lax.scan over "super-blocks"
(one repetition of the config's layer pattern, params stacked on a leading
axis under the "stack" key), so an 80-layer model lowers a single block body
once — essential for CPU-hosted 512-device SPMD compiles.  Layers that don't
fill a whole super-block live unstacked under "rest_i" keys.

Heterogeneous patterns (jamba's 1:7 mamba:attn with alternating MoE,
gemma3's 5:1 local:global) unroll the pattern INSIDE the scan body.

Activation-checkpoint policy per cfg.remat: "none" | "dots" | "full",
applied to the super-block body.

AP serving (an active :func:`repro.apc.layers.ap_serving` context): the
bank's projections are host-orchestrated and cannot run under ``lax.scan``
or ``jax.checkpoint`` (traced, they would fall back to float), so
``forward`` and ``decode_step`` then run the stack layer by layer, each
layer marked with its index (:func:`repro.apc.layers.ap_layer`).
"""
from __future__ import annotations

import contextlib
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig
from ..configs.shapes import ShapeCell
from . import blocks as blk
from .common import DATA_AXES, dtype_of, embed_init, dense_init, rms_norm

Params = dict


def _constrain(x: jax.Array, mesh, *rest) -> jax.Array:
    """Constrain x to P(data_axes, *rest); skipped when mesh is None (e.g.
    inside the compressed-DP shard_map where axes are already mapped).
    data_axes adapts to the mesh: ("pod","data") multi-pod, ("data",)
    single-pod."""
    if mesh is None:
        return x
    da = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    return jax.lax.with_sharding_constraint(x, P(da, *rest))


# ---------------------------------------------------------------------------
# Structure helpers
# ---------------------------------------------------------------------------

def _layer_plan(cfg: ModelConfig) -> tuple[int, list[tuple[str, str]], int]:
    """(n_superblocks, pattern [(mixer, ffn)] , n_rest_layers)."""
    period = cfg.pattern_period
    pattern = [(cfg.mixer_at(i), cfg.ffn_at(i)) for i in range(period)]
    n_sb = cfg.n_layers // period
    n_rest = cfg.n_layers - n_sb * period
    return n_sb, pattern, n_rest


def _ap_layers() -> bool:
    """True while an AP serving context is active (see the module doc)."""
    from ..apc.layers import current_ap_context
    return current_ap_context() is not None


def _layer_scope(index: int | None):
    """Mark the layer being served for the AP context (nothing when None)."""
    if index is None:
        return contextlib.nullcontext()
    from ..apc.layers import ap_layer
    return ap_layer(index)


def _served(p: Params, cdt) -> Params:
    """A layer's parameters as its step computes with them: float leaves
    in the compute dtype, integer leaves (packed ternary weights) as
    they are."""
    return jax.tree.map(lambda a: a.astype(cdt) if a.dtype
                        in (jnp.float32, jnp.bfloat16) else a, p)


def remat_wrap(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "full":
        return jax.checkpoint(fn,
                              policy=jax.checkpoint_policies.nothing_saveable)
    return jax.checkpoint(
        fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    dtype = dtype_of(cfg.param_dtype)
    n_sb, pattern, n_rest = _layer_plan(cfg)
    keys = jax.random.split(key, 8)
    cross = cfg.enc_layers > 0
    params: Params = {
        "embed": {"table": embed_init(keys[0], (cfg.vocab, cfg.d_model),
                                      dtype)},
        "final_norm": jnp.ones((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"w": dense_init(
            keys[1], (cfg.d_model, cfg.vocab), 0, dtype)}

    def make_stacked(key, kinds: tuple[str, str], n: int, use_cross: bool):
        def one(k):
            return blk.init_block(k, cfg, kinds[0], kinds[1],
                                  cross=use_cross, dtype=dtype)
        return jax.vmap(one)(jax.random.split(key, n))

    if n_sb > 0:
        stack = {}
        pk = jax.random.split(keys[2], len(pattern))
        for i, kinds in enumerate(pattern):
            stack[f"pos_{i}"] = make_stacked(pk[i], kinds, n_sb, cross)
        params["stack"] = stack
    rk = jax.random.split(keys[3], max(n_rest, 1))
    for j in range(n_rest):
        kinds = pattern[j % len(pattern)]
        params[f"rest_{j}"] = blk.init_block(rk[j], cfg, kinds[0], kinds[1],
                                             cross=cross, dtype=dtype)
    if cfg.enc_layers:
        ek = jax.random.split(keys[4], 2)
        params["enc_stack"] = {"pos_0": make_stacked(
            ek[0], ("attn", "mlp"), cfg.enc_layers, False)}
        params["enc_norm"] = jnp.ones((cfg.d_model,), dtype)
    return params


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------

def embed_tokens(cfg: ModelConfig, params: Params, tokens: jax.Array,
                 embeds: jax.Array | None) -> jax.Array:
    cdt = dtype_of(cfg.compute_dtype)
    x = params["embed"]["table"].astype(cdt)[tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, cdt)
    if embeds is not None:                       # vlm/audio frontend stub
        x = jnp.concatenate([embeds.astype(cdt), x], axis=1)
    return x


def _run_stack(cfg: ModelConfig, params: Params, x: jax.Array, positions,
               mesh, causal: bool, enc_out=None, prefix: str = "",
               n_layers: int | None = None) -> jax.Array:
    """Scan the (prefix-named) stacked blocks + remainder blocks over x."""
    n_sb, pattern, n_rest = _layer_plan(cfg)
    if prefix == "enc_":
        n_sb, pattern, n_rest = cfg.enc_layers, [("attn", "mlp")], 0
    cdt = dtype_of(cfg.compute_dtype)
    ap = _ap_layers()
    period = len(pattern)
    # AP layer index of the stack's first layer: encoder layers follow the
    # decoder's, so no two layers share a key of the AP context
    first = cfg.n_layers if prefix == "enc_" else 0

    def sb_body(x, sb_params, sb=None):
        for i, (mk, fk) in enumerate(pattern):
            p_i = _served(sb_params[f"pos_{i}"], cdt)
            with _layer_scope(None if sb is None
                              else first + sb * period + i):
                x = blk.block_forward(p_i, x, cfg, mk, fk, positions, mesh,
                                      causal=causal, enc_out=enc_out)
        x = _constrain(x, mesh, None, None)
        return x, None

    body = remat_wrap(sb_body, cfg)
    stack_key = prefix + "stack"
    if stack_key in params and n_sb > 0:
        if ap:                 # AP serving: layer by layer, never traced
            for sb in range(n_sb):
                x, _ = sb_body(x, jax.tree.map(lambda a: a[sb],
                                               params[stack_key]), sb)
        elif n_sb <= 2:        # unrolled: exact cost analysis (dry-run probes)
            for sb in range(n_sb):
                x, _ = body(x, jax.tree.map(lambda a: a[sb],
                                            params[stack_key]))
        else:
            x, _ = jax.lax.scan(lambda c, p: body(c, p), x,
                                params[stack_key])
    for j in range(n_rest):
        mk, fk = pattern[j % len(pattern)]
        p_j = _served(params[f"rest_{j}"], cdt)
        with _layer_scope(first + n_sb * period + j if ap else None):
            x = blk.block_forward(p_j, x, cfg, mk, fk, positions, mesh,
                                  causal=causal, enc_out=enc_out)
    return x


def forward(cfg: ModelConfig, params: Params, batch: dict, mesh
            ) -> jax.Array:
    """batch: tokens [B, S_tok], optional embeds [B, n_front, d],
    optional enc_tokens/enc_embeds for enc-dec.  Returns logits [B, S, V]."""
    cdt = dtype_of(cfg.compute_dtype)
    tokens = batch["tokens"]
    x = embed_tokens(cfg, params, tokens, batch.get("embeds"))
    x = _constrain(x, mesh, None, None)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))

    enc_out = None
    if cfg.enc_layers:
        enc_in = batch.get("enc_embeds")
        if enc_in is None:
            enc_in = params["embed"]["table"].astype(cdt)[batch["enc_tokens"]]
        e_pos = jnp.broadcast_to(
            jnp.arange(enc_in.shape[1])[None, :], enc_in.shape[:2])
        enc_out = _run_stack(cfg, params, enc_in.astype(cdt), e_pos, mesh,
                             causal=False, prefix="enc_")
        enc_out = rms_norm(enc_out, params["enc_norm"].astype(cdt),
                           cfg.norm_eps)

    x = _run_stack(cfg, params, x, positions, mesh, causal=True,
                   enc_out=enc_out)
    x = rms_norm(x, params["final_norm"].astype(cdt), cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = x @ params["embed"]["table"].astype(cdt).T
    else:
        logits = x @ params["lm_head"]["w"].astype(cdt)
    logits = _constrain(logits, mesh, None, "model")
    return logits


# ---------------------------------------------------------------------------
# Serving: cache init + decode step (+ prefill)
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, seq_len: int,
               cross_len: int = 0, dtype=jnp.bfloat16) -> dict:
    n_sb, pattern, n_rest = _layer_plan(cfg)
    cross_len = cross_len if cfg.enc_layers else 0

    cache: dict = {}
    if n_sb > 0:
        stack = {}
        for i, (mk, _) in enumerate(pattern):
            one = blk.init_block_cache(cfg, mk, batch, seq_len, cross_len,
                                       dtype)
            stack[f"pos_{i}"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (n_sb, *a.shape)), one)
        cache["stack"] = stack
    for j in range(n_rest):
        mk, _ = pattern[j % len(pattern)]
        cache[f"rest_{j}"] = blk.init_block_cache(cfg, mk, batch, seq_len,
                                                  cross_len, dtype)
    return cache


def decode_step(cfg: ModelConfig, params: Params, cache: dict,
                tokens: jax.Array, pos: jax.Array, mesh
                ) -> tuple[jax.Array, dict]:
    """One decode step: tokens [B] int32, pos scalar -> (logits [B, V], cache)."""
    cdt = dtype_of(cfg.compute_dtype)
    n_sb, pattern, n_rest = _layer_plan(cfg)
    x = params["embed"]["table"].astype(cdt)[tokens][:, None, :]
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, cdt)
    x = _constrain(x, mesh, None, None)

    ap = _ap_layers()
    period = len(pattern)

    def sb_body(x, scanned, sb=None):
        sb_params, sb_cache = scanned
        new_cache = {}
        for i, (mk, fk) in enumerate(pattern):
            p_i = _served(sb_params[f"pos_{i}"], cdt)
            with _layer_scope(None if sb is None else sb * period + i):
                x, new_cache[f"pos_{i}"] = blk.block_decode(
                    p_i, x, sb_cache[f"pos_{i}"], cfg, mk, fk, pos, mesh)
        return x, new_cache

    new_cache: dict = {}
    if n_sb > 0:
        if n_sb <= 2 or ap:    # AP serving: layer by layer, never traced
            outs = []
            for sb in range(n_sb):
                x, c_sb = sb_body(x, jax.tree.map(
                    lambda a: a[sb], (params["stack"], cache["stack"])),
                    sb if ap else None)
                outs.append(c_sb)
            new_cache["stack"] = jax.tree.map(
                lambda *xs: jnp.stack(xs), *outs)
        else:
            x, new_cache["stack"] = jax.lax.scan(
                sb_body, x, (params["stack"], cache["stack"]))
    for j in range(n_rest):
        mk, fk = pattern[j % len(pattern)]
        p_j = _served(params[f"rest_{j}"], cdt)
        with _layer_scope(n_sb * period + j if ap else None):
            x, new_cache[f"rest_{j}"] = blk.block_decode(
                p_j, x, cache[f"rest_{j}"], cfg, mk, fk, pos, mesh)
    x = rms_norm(x, params["final_norm"].astype(cdt), cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = x[:, 0] @ params["embed"]["table"].astype(cdt).T
    else:
        logits = x[:, 0] @ params["lm_head"]["w"].astype(cdt)
    logits = _constrain(logits, mesh, "model")
    return logits, new_cache


def prepare_ap(cfg: ModelConfig, params: Params, ctx) -> Params:
    """Convert, once, what an AP serving context serves from the weights
    as given, keyed by layer index: every MoE layer's held experts (see
    :func:`repro.models.moe.prepare_moe_ap`) and every packed dense MLP's
    projections, as its step serves them (see
    :func:`repro.models.mlp.prepare_mlp_ap`).  Returns the parameters to
    serve: ``params`` without the converted expert weights, which the
    bank holds from now on (the same leaves as given for dense models)."""
    from .mlp import prepare_mlp_ap
    from .moe import EXPERT_LEAVES, prepare_moe_ap

    cdt = dtype_of(cfg.compute_dtype)

    def ffn_of(p, fk):
        """The leaves of a layer's FFN that the bank serves."""
        if fk == "moe":
            return {n: a for n, a in p["moe"].items() if n in EXPERT_LEAVES}
        return p["mlp"] if "w1_packed" in p["mlp"] else {}

    def convert(layer, fk, ffn):
        if fk == "moe":
            prepare_moe_ap(ctx, layer, ffn)
        elif ffn:
            prepare_mlp_ap(ctx, layer, _served(ffn, cdt))

    def drop(p):
        moe = {n: a for n, a in p["moe"].items() if n not in EXPERT_LEAVES}
        return dict(p, moe=moe)

    n_sb, pattern, n_rest = _layer_plan(cfg)
    period = len(pattern)
    out = dict(params)
    for i, (_, fk) in enumerate(pattern):
        if fk not in ("moe", "mlp") or "stack" not in params:
            continue
        pos = params["stack"][f"pos_{i}"]
        ffn = ffn_of(pos, fk)
        for sb in range(n_sb):
            convert(sb * period + i, fk, {n: a[sb] for n, a in ffn.items()})
        if fk == "moe":
            out["stack"] = dict(out["stack"], **{f"pos_{i}": drop(pos)})
    for j in range(n_rest):
        fk = pattern[j % period][1]
        if fk in ("moe", "mlp"):
            convert(n_sb * period + j, fk, ffn_of(params[f"rest_{j}"], fk))
        if fk == "moe":
            out[f"rest_{j}"] = drop(params[f"rest_{j}"])
    return out


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStruct stand-ins for the dry-run)
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, cell: ShapeCell,
                cache_dtype=jnp.bfloat16) -> dict:
    """ShapeDtypeStructs for every model input of the given shape cell."""
    b, s = cell.global_batch, cell.seq_len
    i32 = jnp.int32
    sds = jax.ShapeDtypeStruct
    if cell.kind in ("train", "prefill"):
        n_front = cfg.n_frontend_tokens if cfg.frontend else 0
        spec = {"tokens": sds((b, s - n_front), i32)}
        if cfg.frontend:
            spec["embeds"] = sds((b, n_front, cfg.d_model),
                                 dtype_of(cfg.compute_dtype))
        if cfg.enc_layers:
            enc_len = min(s, 4096)
            spec["enc_embeds"] = sds((b, enc_len, cfg.d_model),
                                     dtype_of(cfg.compute_dtype))
        if cell.kind == "train":
            spec["targets"] = sds((b, s - n_front), i32)
        return spec
    # decode: one token against a seq_len cache
    cross_len = min(s, 4096) if cfg.enc_layers else 0
    cache = jax.eval_shape(
        lambda: init_cache(cfg, b, s, cross_len, cache_dtype))
    return {"tokens": sds((b,), i32),
            "pos": sds((), i32),
            "cache": cache}
