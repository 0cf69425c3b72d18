"""Chunked selective scan (Pallas TPU): Mamba-1's recurrence over one
chunk of one sequence.

    s_t = exp(dt_t * A) * s_{t-1} + (dt_t * x_t) (x) B_t
    y_t = sum_n s_t[n] * C_t[n]

with ``x``, ``dt`` ``[C, Di]`` (positions, channels), ``A`` ``[N, Di]``
(states, channels), ``B``, ``Cm`` ``[C, N]`` and the state ``s`` ``[N,
Di]`` float32 carried in and out. A position whose ``dt`` is 0 leaves the
state as it is (``exp(0) = 1`` and nothing is added): a padded last chunk
ends with the state of its last real position.

The recurrence is a chain over positions, so walked a position at a time
in XLA it is bound by latency (a loop iteration a position over 320 KB of
state), and as an associative scan it moves the ``[C, Di, N]`` products
through HBM eight times over. Here the grid is over tiles of channels,
which are independent: a program holds its ``[N, tile]`` state in
registers (states on the sublanes, channels on the lanes: a tile of 512
channels is 8 vregs) and walks the chunk's positions, eight to a loop
iteration. ``B_t`` and ``C_t`` arrive broadcast over 128 lanes (``[C, N,
128]``, 2 MB each at a chunk of 256, made by XLA, fetched once: their
block index never changes), so a position's ``[N, 128]`` tile is one load
and no in-kernel transpose.

:func:`selective_scan_reference` is the same recurrence as a
``lax.scan`` a position at a time (``use_kernel=False``, and what the
kernel is tested against).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._mosaic import x64_off

__all__ = ["selective_scan_chunk", "selective_scan_reference",
           "channel_tile"]

_LANES = 128
_ROWS = 8           # positions a loop iteration


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def channel_tile(d_inner: int) -> int:
    """Channels a program: 512 where they divide, else 128s, else all."""
    for tile in (512, 256, 128):
        if d_inner % tile == 0:
            return tile
    return d_inner


def _scan_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, s0_ref, y_ref, s_ref,
                 *, chunk, reps):
    """One channel tile: ``x``, ``dt``, ``y`` ``[C, tile]``, ``a``, ``s0``,
    ``s`` ``[N, tile]``, ``b``, ``c`` ``[C, N, lanes]`` (``reps`` lane
    groups side by side make a tile)."""
    a = a_ref[...]

    def wide(v):                    # [N, lanes] -> [N, tile]
        return v if reps == 1 else jnp.concatenate([v] * reps, axis=1)

    def eight(i, s):
        t0 = pl.multiple_of(i * _ROWS, _ROWS)
        xs = x_ref[pl.ds(t0, _ROWS), :]
        dts = dt_ref[pl.ds(t0, _ROWS), :]
        ys = []
        for r in range(_ROWS):
            dt = dts[r:r + 1, :]                        # [1, tile]
            s = jnp.exp(dt * a) * s \
                + (dt * xs[r:r + 1, :]) * wide(b_ref[t0 + r])
            ys.append(jnp.sum(s * wide(c_ref[t0 + r]), axis=0,
                              keepdims=True))
        y_ref[pl.ds(t0, _ROWS), :] = jnp.concatenate(ys, axis=0)
        return s

    s_ref[...] = jax.lax.fori_loop(0, chunk // _ROWS, eight, s0_ref[...])


def selective_scan_chunk(x, dt, A, B, Cm, s0, interpret=None):
    """See the module docstring. Returns ``(y [C, Di] float32, s [N, Di]
    float32)``."""
    C, Di = x.shape
    N = A.shape[0]
    if interpret is None:
        interpret = _interpret()
    tile = channel_tile(Di)
    lanes = min(_LANES, tile)
    Cp = -(-C // _ROWS) * _ROWS
    f32 = jnp.float32
    with x64_off(interpret):
        x, dt = x.astype(f32), dt.astype(f32)
        B, Cm = B.astype(f32), Cm.astype(f32)
        if Cp != C:                 # dt = 0: the state stays
            pad = [(0, Cp - C), (0, 0)]
            x, dt, B, Cm = (jnp.pad(v, pad) for v in (x, dt, B, Cm))
        wide = lambda v: jnp.broadcast_to(v[:, :, None], (Cp, N, lanes))
        rows = pl.BlockSpec((Cp, tile), lambda j: (0, j))
        state = pl.BlockSpec((N, tile), lambda j: (0, j))
        coef = pl.BlockSpec((Cp, N, lanes), lambda j: (0, 0, 0))
        # x, dt, y in and out twice over, and B, C: stated where the
        # default scoped VMEM would be near
        need = 4 * (2 * 3 * Cp * tile + 2 * 2 * Cp * N * lanes)
        y, s = pl.pallas_call(
            functools.partial(_scan_kernel, chunk=Cp, reps=tile // lanes),
            grid=(Di // tile,),
            in_specs=[rows, rows, state, coef, coef, state],
            out_specs=[rows, state],
            out_shape=[jax.ShapeDtypeStruct((Cp, Di), f32),
                       jax.ShapeDtypeStruct((N, Di), f32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",),
                vmem_limit_bytes=max(32 << 20, 2 * need)),
            interpret=interpret,
            name="selective_scan_chunk",
        )(x, dt, A.astype(f32), wide(B), wide(Cm), s0.astype(f32))
    return y[:C], s


def selective_scan_reference(x, dt, A, B, Cm, s0):
    """The recurrence a position at a time, float32."""
    f32 = jnp.float32
    A = A.astype(f32)

    def step(s, xs):
        x_t, dt_t, b_t, c_t = xs
        s = jnp.exp(dt_t[None] * A) * s + (dt_t * x_t)[None] * b_t[:, None]
        return s, jnp.sum(s * c_t[:, None], axis=0)

    s, y = jax.lax.scan(step, s0.astype(f32),
                        (x.astype(f32), dt.astype(f32), B.astype(f32),
                         Cm.astype(f32)))
    return y, s
