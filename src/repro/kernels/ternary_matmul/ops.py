"""Public jit'd wrapper for the packed-ternary matmul (handles padding)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..tap_pass.kernel import resolve_interpret
from .kernel import BK, BM, BN
from .kernel import ternary_matmul as _ternary_matmul_kernel
from .ref import PACK, pack_ternary, quantize_ternary, ternary_matmul_ref


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    target = (size + mult - 1) // mult * mult
    if target == size:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, target - size)
    return jnp.pad(x, pad)


def ternary_matmul_op(x: jax.Array, packed: jax.Array, scale: jax.Array,
                      interpret: bool | None = None) -> jax.Array:
    """y = (x @ unpack(packed)) * scale with automatic tile padding.

    x [M, K] float; packed [K/16, N] int32; scale [N] fp32 -> y [M, N].
    ``interpret=None`` resolves per backend like the program kernel
    (compiled Mosaic on TPU, the pallas interpreter elsewhere).
    """
    m, k = x.shape
    n = packed.shape[1]
    kp = packed.shape[0] * PACK
    if k < kp:                   # pack-time padding rows (zero weights)
        x = _pad_to(x, 1, kp) if kp % 16 == 0 else x
        x = x[:, :kp]
    bm = min(BM, max(8, m))      # small-M decode batches: shrink the M tile
    if m % bm:
        x = _pad_to(x, 0, bm)
    xk = _pad_to(x, 1, BK)
    if xk.shape[1] != kp:
        packed = jnp.concatenate(
            [packed, jnp.full(((xk.shape[1] - kp) // PACK, n),
                              0x55555555, dtype=jnp.int32)], axis=0)
        # 0b01 repeated = ternary 0 everywhere: zero padding weights
    pn = _pad_to(packed, 1, BN)
    sn = _pad_to(scale.reshape(-1), 0, BN)
    y = _ternary_matmul_kernel(xk, pn, sn, bm=bm,
                               interpret=resolve_interpret(interpret))
    return y[:m, :n]


def ternary_matmul(x: jax.Array, packed: jax.Array, scale: jax.Array,
                   impl: str = "pallas", **kw) -> jax.Array:
    """Backend dispatcher: y = (x @ unpack(packed)) * scale.

    ``impl`` selects the backend — "pallas" (packed-weight tiled kernel,
    :func:`ternary_matmul_op`), "ref" (pure-jnp oracle), or "ap" (the
    associative-processor MAC program, :func:`~repro.kernels.ternary_matmul.
    ap.ternary_matmul_ap`; extra kwargs like radix/width/mesh/stats pass
    through).  See the package docstring for when each wins.
    """
    if impl in ("pallas", "packed"):
        return ternary_matmul_op(x, packed, scale, **kw)
    if impl == "ref":
        if kw:
            raise TypeError(f"impl='ref' takes no extra kwargs, got {kw}")
        return ternary_matmul_ref(x, packed, scale)
    if impl == "ap":
        from .ap import ternary_matmul_ap
        return ternary_matmul_ap(x, packed, scale, **kw)
    raise ValueError(f"unknown impl {impl!r}; use 'pallas', 'ref', or 'ap'")


def quantize_and_pack(w: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Dense fp weights [K, N] -> (packed int32 [K'/16, N], scale [N])."""
    k = w.shape[0]
    w = _pad_to(w, 0, PACK)
    w_ter, scale = quantize_ternary(w)
    if w.shape[0] != k:                  # padded rows must quantize to 0
        w_ter = w_ter.at[k:].set(0)
    return pack_ternary(w_ter), scale


__all__ = ["ternary_matmul", "ternary_matmul_op", "quantize_and_pack",
           "pack_ternary", "quantize_ternary", "ternary_matmul_ref", "PACK"]
