"""Packed-ternary matmul Pallas kernel.

Grid (m, n, k) with k innermost; BlockSpecs stage

    x      (bm, bk)        activations, input dtype
    packed (bk/16, bn)     int32, 16 ternary weights per word
    scale  (1, bn)         fp32 per-channel scale
    out    (bm, bn)        written on the final k step
    acc    (bm, bn) fp32   VMEM scratch accumulator

into VMEM.  The 2-bit weights are unpacked in-register (shift/mask on the
int32 words — VPU work) and fed to the MXU via jnp.dot in fp32.  HBM traffic
for weights is K*N/4 bytes instead of 2*K*N (bf16): the memory-roofline term
of a weight-bound decode step drops ~8x.

Block shape notes: bm/bn multiples of 128 keep the MXU matmul dims aligned;
bk = 256 keeps the unpacked (bk, bn) fp32 tile at 128 KB and the whole
working set (x + packed + unpacked + acc) under ~1 MB of VMEM, leaving
headroom for the pipeline's double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import PACK

BM, BN, BK = 128, 128, 256


def _ternary_matmul_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    packed = w_ref[...]                                 # [bk/16, bn] int32
    u = packed.astype(jnp.uint32)
    # unpack 16 2-bit digits per word -> [bk/16, 16, bn] -> [bk, bn]
    shifts = (2 * jnp.arange(PACK, dtype=jnp.uint32))[None, :, None]
    digits = (u[:, None, :] >> shifts) & jnp.uint32(3)
    w = (digits.astype(jnp.int32) - 1).astype(jnp.float32)
    w = w.reshape(packed.shape[0] * PACK, packed.shape[1])

    x = x_ref[...].astype(jnp.float32)
    acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[...] = (acc_ref[...] * s_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def ternary_matmul(x: jax.Array, packed: jax.Array, scale: jax.Array,
                   bm: int = BM, bn: int = BN, bk: int = BK, *,
                   interpret: bool) -> jax.Array:
    """y[M, N] = (x[M, K] @ unpack(packed)) * scale, tiled on TPU.

    Shapes must tile exactly: M % bm == 0, N % bn == 0, K % bk == 0,
    bk % 16 == 0.  (The ops.py wrapper pads and resolves ``interpret``.)
    """
    m, kdim = x.shape
    k16, n = packed.shape
    if k16 * PACK != kdim:
        raise ValueError(f"packed K {k16 * PACK} != x K {kdim}")
    if m % bm or n % bn or kdim % bk or bk % PACK:
        raise ValueError(f"bad tiling {(m, n, kdim)} vs {(bm, bn, bk)}")
    n_k = kdim // bk
    grid = (m // bm, n // bn, n_k)
    return pl.pallas_call(
        functools.partial(_ternary_matmul_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk // PACK, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, packed, scale.reshape(1, -1))
