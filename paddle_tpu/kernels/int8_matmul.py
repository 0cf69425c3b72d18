"""Fused weight-only-int8 dequant-matmul kernel (Pallas TPU).

The serving engines store decode matmul weights as int8 with
per-output-channel f32 scales (:mod:`paddle_tpu.quantization.export`);
the XLA path dequantizes in the graph (``convert(int8->f32) ->
dot_general -> mul(scale)``), which the static cost model prices as an
extra materialized matmul output before the scale multiply. This kernel
streams the int8 weight into VMEM, dequantizes **in registers** on the
MXU feed, accumulates in f32 scratch, and applies the scale on the
final write — one HBM read of the int8 buffer, one write of the result.

Layout contract (the auto-fusion rewrite's canonical 2-D form — callers
with higher-rank einsums flatten/transpose around this call):

- ``x``     ``[M, K]`` float (f32/bf16) activations.
- ``w``     ``[K, N]`` int8 weight, contraction leading.
- ``scale`` ``[N]`` float per-output-channel scales.

Returns ``[M, N]`` in ``x``'s dtype, numerically matching the engines'
``(x @ w.astype(dt)) * scale`` post-scaled einsum.

This is the target template of the ``int8_dequant_matmul`` auto-fusion
rewrite rule (:mod:`paddle_tpu.analysis.rewrite`); the ``pallas_call``
is named ``autofuse_int8_matmul`` so the cost pass recognizes rewritten
programs (PTCS005). On CPU the kernel runs in interpreter mode; on TPU
``M`` pads to the 8-sublane multiple and ``K``/``N`` to the 128-lane
width (int8 tiles want ``K`` in 32-row packs).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._mosaic import x64_off

__all__ = ["int8_matmul"]

_LANE = 128

_ARB3 = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _mm_kernel(x_ref, w_ref, s_ref, o_ref, acc_scr, *, nk):
    """One (m block, n block, k block) step: dequantize the int8 weight
    tile in registers, accumulate x @ w in f32 scratch, scale on the
    last k step."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)   # in-register dequant
    acc_scr[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _():
        o_ref[...] = (acc_scr[...]
                      * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _pad_to(n: int, m: int) -> int:
    return -(-n // m) * m


def int8_matmul(x, w, scale, interpret=None):
    """``(x [M,K] float) @ (w [K,N] int8) * (scale [N]) -> [M,N]`` with
    the dequant fused into the matmul feed (see module docstring)."""
    M, K = x.shape
    K2, N = w.shape
    if K != K2 or scale.shape != (N,):
        raise ValueError(f"int8_matmul shape mismatch: x {x.shape}, "
                         f"w {w.shape}, scale {scale.shape}")
    if interpret is None:
        interpret = _interpret()
    if interpret:
        Mp, Kp, Np = M, K, N
        bm, bk, bn = M, K, N
    else:
        bm = min(_pad_to(M, 8), 256)
        bk = min(_pad_to(K, 32), 512)
        bn = min(_pad_to(N, _LANE), 512)
        Mp, Kp, Np = _pad_to(M, bm), _pad_to(K, bk), _pad_to(N, bn)
        if (Mp, Kp) != (M, K):
            x = jnp.pad(x, [(0, Mp - M), (0, Kp - K)])
        if (Kp, Np) != (K, N):
            w = jnp.pad(w, [(0, Kp - K), (0, Np - N)])
        if Np != N:
            scale = jnp.pad(scale, [(0, Np - N)])
    nk = Kp // bk
    with x64_off(interpret):
        out = pl.pallas_call(
            functools.partial(_mm_kernel, nk=nk),
            grid=(Mp // bm, Np // bn, nk),
            in_specs=[
                pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
                pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
                pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
            out_shape=jax.ShapeDtypeStruct((Mp, Np), x.dtype),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
            compiler_params=_ARB3,
            interpret=interpret,
            name="autofuse_int8_matmul",
        )(x, w, scale.reshape(1, -1))
    return out[:M, :N]
