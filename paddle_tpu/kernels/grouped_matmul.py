"""Grouped matmul over rows sorted by group (Pallas TPU): the expert
product of a mixture layer that never drops a token.

``lhs`` ``[M, K]`` holds the rows of group 0, then of group 1, and so on
(``counts`` ``[G]`` rows each); ``rhs`` is a stack of weights ``[S, K,
N]`` of which this call reads the ``G`` matrices from ``layer * G`` on:
row ``i`` of group ``g`` is multiplied by ``rhs[layer * G + g]``. The
stack is read where it lies: ``layer`` (a traced int32 scalar) rides the
scalar prefetch and the weight block's index is ``layer * G + group``,
so the serving programs hand every layer the whole ``[L * E, ...]``
stack and nothing cuts a layer's experts out of it, and the metadata is
sized by the ``G`` groups of one layer.

The scheme is that of the ``megablox`` ``gmm`` that ships with JAX
(``jax/experimental/pallas/ops/tpu/megablox/gmm.py``): the rows are cut
into tiles of ``tm``; a *visit* is one (row tile, group) pair that share
a row, so a tile that holds the edge of two groups is visited twice;
group offsets, and the group and the row tile of every visit, are
scalar-prefetch arrays; the grid is ``(n tiles, visits, k tiles)`` with
the number of visits a traced value, an f32 accumulator in VMEM over the
k tiles, and a store that a mask holds to the rows of the visit's group.
Visits of one row tile are consecutive, so its output block stays in
VMEM between them.

**The row tile follows the shapes** (:func:`row_tile`): 128 rows unless
a group gets several times that on average. XLA's own ``ragged_dot``
kernel takes row tiles of 512 whatever the rows: where 1,024 rows
spread over ~100 experts that is ~100 visits of 512 rows, fifty times
the rows there are, and the product was bound by that padding work
(24.5 us a visited expert a layer on a v5e, PR 31) and not by the
experts' bytes. A visit costs the MXU its tile's rows and the memory
the expert's weights, so the tile is as small as keeps the MXU's rows
filled. The weight tile is the whole ``N`` wide where that fits, so a
visit reads its rows once.

On CPU the kernel runs in interpreter mode; :func:`grouped_matmul_reference`
is the same product through ``jax.lax.ragged_dot`` (the groups of every
other layer empty), the path ``use_kernel=False`` keeps and the tests
compare with. The trace calls the kernel ``grouped_ragged-dot``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._mosaic import x64_off

__all__ = ["GroupRows", "row_tile", "group_rows", "grouped_matmul",
           "grouped_matmul_reference", "tile_visits"]

# a weight tile [tk, tn] in bytes: two of them in flight beside the rows,
# the output block and the accumulator. An SDAR expert's gate and up
# [2048, 1536] in bf16 are one tile: a visit is one grid step and the sum
# over K is one product (tiles of 1.5 and 3 MB read 10% slower, PR 31)
_WEIGHT_TILE_BYTES = 6 << 20
_TN_MAX = 2048


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def row_tile(m: int, groups: int) -> int:
    """The row tile for ``m`` sorted rows over ``groups`` groups: 128,
    doubled while a group's mean share of the rows fills two tiles, up
    to 512."""
    tm = 128
    while tm < 512 and m // groups >= 2 * tm:
        tm *= 2
    return tm


def _divisor_tile(dim: int, cap: int) -> int:
    """The whole ``dim`` where it is within ``cap``, else its largest
    divisor within ``cap`` that is a multiple of 128 (lanes; and
    sublanes of any type), else the whole ``dim``."""
    if dim <= cap:
        return dim
    for t in range(cap // 128 * 128, 0, -128):
        if dim % t == 0:
            return t
    return dim


class GroupRows(NamedTuple):
    """What the kernel's index maps read, once for every product over
    the same groups: ``offsets`` ``[G + 1]`` (group ``g`` holds rows
    ``offsets[g]`` to ``offsets[g + 1]``), the ``group_ids`` and
    ``tile_ids`` ``[m / tm + G - 1]`` of every visit in grid order (past
    ``visits`` never read), ``visits`` ``[1]``, the grid's traced
    extent; ``tm`` and the padded row count ``m`` are static."""
    offsets: jax.Array
    group_ids: jax.Array
    tile_ids: jax.Array
    visits: jax.Array
    tm: int
    m: int


def _group_tiles(counts, tm: int):
    """``(ends, tiles)`` of groups of ``counts`` ``[..., G]`` rows laid
    one after another from row 0 (NumPy or JAX arrays alike): the row
    past each group's last, and the row tiles it shares a row with, from
    the one its first row lies in to the one its last row lies in."""
    ends = counts.cumsum(-1)
    starts = ends - counts
    return ends, (counts > 0) * (-(-ends // tm) - starts // tm)


def group_rows(counts, m: int) -> GroupRows:
    """The visits of ``m`` rows sorted into ``counts`` ``[G]`` (int32,
    summing to at most ``m``; rows past the sum belong to no group and
    are never stored). A few dense comparisons of ``[visits, G]``: on
    the chip a scatter or a ``repeat`` of 128 elements costs more than
    the product of a small batch."""
    G = counts.shape[0]
    tm = row_tile(m, G)
    tiles_m = -(-m // tm)
    i32 = jnp.int32
    counts = counts.astype(i32)
    ends, n_tiles = _group_tiles(counts, tm)
    last = n_tiles.cumsum()                     # one past its last visit
    first = last - n_tiles
    v = jnp.arange(tiles_m + G - 1, dtype=i32)[:, None]
    group_ids = jnp.sum(last[None] <= v, 1, dtype=i32)
    # the rows of the groups that got any are contiguous, so a visit is
    # of the tile after the last visit's, or of the same tile where its
    # group starts inside one
    inside = (counts > 0) & ((ends - counts) % tm != 0)
    tile_ids = v[:, 0] - jnp.sum(inside[None] & (first[None] <= v), 1,
                                 dtype=i32)
    return GroupRows(jnp.concatenate([jnp.zeros((1,), i32), ends]),
                     jnp.minimum(group_ids, G - 1),
                     jnp.minimum(tile_ids, tiles_m - 1),
                     last[-1:], tm, tiles_m * tm)


def tile_visits(counts, tm: int) -> int:
    """:func:`group_rows`'s number of visits, on the host: ``counts``
    ``[..., G]``, the groups of each leading index sorted from row 0."""
    return int(_group_tiles(np.asarray(counts, np.int64), tm)[1].sum())


def _kernel(offsets, group_ids, tile_ids, layer, lhs, rhs, out, acc, *,
            tm, tiles_k):
    del layer
    v, k_i = pl.program_id(1), pl.program_id(2)

    @pl.when(k_i == 0)
    def _zero():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += jnp.dot(lhs[...], rhs[...],
                        preferred_element_type=jnp.float32)

    @pl.when(k_i == tiles_k - 1)
    def _store():
        g = group_ids[v]
        row = tile_ids[v] * tm + jax.lax.broadcasted_iota(
            jnp.int32, acc.shape, 0)
        mine = (row >= offsets[g]) & (row < offsets[g + 1])
        out[...] = jnp.where(mine, acc[...],
                             out[...].astype(jnp.float32)).astype(out.dtype)


def grouped_matmul(lhs, rhs, rows: GroupRows, layer=None):
    """``lhs`` ``[M, K]`` (rows sorted by group) times the groups'
    matrices ``rhs[layer * G + g]`` of the stack ``rhs`` ``[S, K, N]``
    (``layer`` None: the stack is the ``G`` matrices): ``[M, N]`` in
    ``lhs.dtype``, operands as they are, accumulated in float32 over the
    whole ``K``. ``rows`` is :func:`group_rows` of the groups' counts and
    ``M``. Rows that belong to no group come out undefined."""
    M, K = lhs.shape
    S, _, N = rhs.shape
    G = rows.offsets.shape[0] - 1
    tm, m = rows.tm, rows.m
    if rhs.shape[1] != K or m < M or (layer is None and S != G):
        raise ValueError(f"lhs {lhs.shape}, rhs {rhs.shape}: rows for "
                         f"{m} x {G} groups")
    size = jnp.dtype(rhs.dtype).itemsize
    tn = _divisor_tile(N, _TN_MAX)
    tk = _divisor_tile(K, max(128, _WEIGHT_TILE_BYTES // (tn * size)))
    tiles_n, tiles_k = N // tn, K // tk
    interpret = _interpret()
    lsize = jnp.dtype(lhs.dtype).itemsize
    vmem = 2 * tk * tn * size + 2 * tm * tk * lsize \
        + 2 * tm * tn * lsize + tm * tn * 4
    layer = jnp.zeros((1,), jnp.int32) if layer is None \
        else jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    with x64_off(interpret):
        if m != M:
            lhs = jnp.pad(lhs, [(0, m - M), (0, 0)])
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(tiles_n, rows.visits[0], tiles_k),
            in_specs=[
                pl.BlockSpec((tm, tk),
                             lambda n, v, k, off, gid, tid, ly:
                             (tid[v], k)),
                pl.BlockSpec((None, tk, tn),
                             lambda n, v, k, off, gid, tid, ly:
                             (ly[0] * G + gid[v], k, n)),
            ],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda n, v, k, off, gid, tid, ly:
                                   (tid[v], n)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        )
        out = pl.pallas_call(
            functools.partial(_kernel, tm=tm, tiles_k=tiles_k),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((m, N), lhs.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary"),
                vmem_limit_bytes=int(vmem * 3 // 2) + (4 << 20)),
            interpret=interpret,
            name="grouped_ragged-dot",
        )(rows.offsets, rows.group_ids, rows.tile_ids, layer, lhs, rhs)
    return out[:M]


def grouped_matmul_reference(lhs, rhs, counts, layer=None):
    """The same product through ``jax.lax.ragged_dot``: against a stack
    of several layers the groups of every other layer are empty, so the
    stack is still read where it lies."""
    G = counts.shape[0]
    counts = counts.astype(jnp.int32)
    if layer is not None:
        counts = jax.lax.dynamic_update_slice(
            jnp.zeros((rhs.shape[0],), jnp.int32), counts,
            (jnp.asarray(layer, jnp.int32) * G,))
    return jax.lax.ragged_dot(lhs, rhs, counts)
