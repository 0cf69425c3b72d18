"""Kernels of learned sparse latent attention (Pallas TPU): the indexer's
scores over the index-key pages, latent attention over the rows a
selection picked, and the writes of new latent rows.

**The latent pool is a pool of 32-bit rows** ``[L, P, page, 1, words]``
(``uint32``). A token's latent row ``[c_kv | k_rope | 0]`` of ``width``
numbers is stored as ``words`` 32-bit words: in bfloat16 two numbers a
word, number ``i`` in the low half of word ``i`` and number ``words + i``
in its high half (:func:`pack_rows`); in float32 a number a word. With
the row alone on its second-minor axis the chip lays the pool out a row
at a time (tiles of ``(1, 128)``), so that one token's row is one
contiguous, tile-aligned slice that a DMA can fetch by itself; in a
``[.., page, width]`` layout a row is a part of a tile of 8 or 16 rows
and cannot be fetched alone. ``words`` is a whole number of 128 lanes.

**The index score** (:func:`index_scores`). ``I[t, s] = sum_j w[t, j]
relu(q[t, j] . k[s])`` for every query ``t`` of a row of the page table
and every position ``s`` of its pages: a grid of (row, query tile, page),
a page of keys ``[page, dI]`` read once for a tile of ``tq`` queries of
``nI`` heads each, the heads' products ``[tq * nI, page]`` weighed and
summed to ``[tq, page]`` in float32. Pages past a row's ``limit`` are not
read (their block index repeats the last live page's, so nothing is
fetched) and come out 0, as do the positions of the last live page past
the limit. A decode tick is ``R`` sequences of one query
(``tq`` 1); a prefill chunk is one sequence of ``C`` queries. In the
trace: ``mla_indexer_decode`` / ``mla_indexer_prefill``.

**The attention of a decode tick** (:func:`sparse_mla`). A grid step a
query: the kernel reads the query's selected positions, looks each one's
page up in the page table (both in scalar memory) and fetches that
token's latent row alone from the pool in HBM by DMA, the next query's
rows while this one's are scored; no other latent row is read. Then its
``nh`` absorbed head rows against its ``K`` rows in one product (a row is
``[c_kv | k_rope | 0]``, a head row ``[q_lat | q_rope | 0]``: one
product is both terms of the score), a softmax over the selected ones,
and the weights against the rows' first ``dc`` numbers (``c_kv``):
``o_lat`` ``[nh, dc]`` in float32. In the trace: ``sparse_mla_decode``.

**The attention of a prefill chunk** (:func:`masked_mla_chunk`). A
chunk's queries each select their own 2,048 rows, and fetching them row
by row for every query is the chunk's cost; so the kernel reads the
sequence's pages in order instead, a grid of (query tile, page) with an
online softmax, and each query's selection is an additive mask on its
scores. In the trace: ``sparse_mla_prefill``.

**The writes** (:func:`write_rows`): each new row DMA'd into its token's
place, the pool aliased in place. In the trace: ``latent_row_write``.

On CPU the kernels run in interpreter mode; ``use_kernel=False`` takes
the XLA reference of each (the tests hold the two equal).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._mosaic import x64_off

__all__ = ["row_words", "pack_rows", "unpack_rows", "write_rows",
           "write_rows_reference", "index_scores", "index_scores_reference",
           "sparse_mla", "sparse_mla_reference", "masked_mla_chunk",
           "masked_mla_chunk_reference"]

_NEG = -1e30


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _index_kernel(pt_ref, lim_ref, ly_ref, q_ref, w_ref, k_ref, o_ref, *,
                  page_size, tq, heads):
    r, j = pl.program_id(0), pl.program_id(2)

    @pl.when(j * page_size < lim_ref[r])
    def _():
        s = jax.lax.dot_general(q_ref[...], k_ref[...],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.maximum(s, 0.0) * w_ref[...]         # [tq * heads, page]
        live = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1) < lim_ref[r]
        o_ref[...] = jnp.where(
            live, jnp.sum(s.reshape(tq, heads, page_size), axis=1), 0.0)

    @pl.when(j * page_size >= lim_ref[r])
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def index_scores(q, w, key_pages, page_table, limits, layer=0, tq=None,
                 name="mla_indexer", use_kernel=True):
    """``I`` ``[R, T, pages * page]`` float32 of ``q`` ``[R, T, nI, dI]``
    and ``w`` ``[R, T, nI]`` (float32, the scales folded in) against the
    keys of ``key_pages`` ``[Lk, P, page, dI]`` at ``layer`` through
    ``page_table`` ``[R, pages]``; positions at or past ``limits`` ``[R]``
    are 0 (the caller masks them). ``tq`` queries share a page's keys in a
    grid step (a divisor of ``T``)."""
    R, T, nI, dI = q.shape
    _, _, ps, _ = key_pages.shape
    pages = page_table.shape[1]
    tq = T if tq is None else tq
    if not use_kernel:
        return index_scores_reference(q, w, key_pages, page_table, limits,
                                      layer)
    if T % tq:
        raise ValueError(f"{T} queries in tiles of {tq}")
    interpret = _interpret()
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    q2 = q.reshape(R, T * nI, dI)
    w2 = w.astype(jnp.float32).reshape(R, T * nI, 1)

    def key_map(r, t, j, pt, lim, ly):
        last = jnp.maximum(lim[r] - 1, 0) // ps
        return ly[0], pt[r, jnp.minimum(j, last)], 0, 0

    vmem = tq * nI * ps * 4 * 3 + 4 * tq * nI * dI * 2 + 4 * ps * dI * 2 \
        + 2 * tq * ps * 4 + 2 * tq * nI * 128 * 4
    with x64_off(interpret):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(R, T // tq, pages),
            in_specs=[
                pl.BlockSpec((None, tq * nI, dI),
                             lambda r, t, j, pt, lim, ly: (r, t, 0)),
                pl.BlockSpec((None, tq * nI, 1),
                             lambda r, t, j, pt, lim, ly: (r, t, 0)),
                pl.BlockSpec((None, None, ps, dI), key_map),
            ],
            out_specs=pl.BlockSpec((None, tq, ps),
                                   lambda r, t, j, pt, lim, ly: (r, t, j)),
        )
        return pl.pallas_call(
            functools.partial(_index_kernel, page_size=ps, tq=tq, heads=nI),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((R, T, pages * ps), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=max(int(vmem * 3 // 2), 16 << 20)),
            interpret=interpret,
            name=name,
        )(page_table.astype(jnp.int32), limits.astype(jnp.int32), layer,
          q2, w2, key_pages)


def index_scores_reference(q, w, key_pages, page_table, limits, layer=0):
    """:func:`index_scores` in plain XLA: every key gathered through the
    table, then the same sum."""
    R, T, nI, dI = q.shape
    _, _, ps, _ = key_pages.shape
    keys = jax.lax.dynamic_index_in_dim(key_pages, jnp.asarray(
        layer, jnp.int32), 0, keepdims=False)[page_table].reshape(
        R, -1, dI)
    s = jnp.einsum("rtjd,rsd->rtjs", q, keys,
                   preferred_element_type=jnp.float32)
    out = jnp.einsum("rtjs,rtj->rts", jnp.maximum(s, 0.0),
                     w.astype(jnp.float32))
    pos = jnp.arange(keys.shape[1], dtype=jnp.int32)
    return jnp.where(pos[None, None] < limits[:, None, None], out, 0.0)


# --------------------------------------------------------------------------
# the pool of 32-bit rows
# --------------------------------------------------------------------------

def row_words(width, dtype):
    """32-bit words a row of ``width`` numbers of ``dtype`` takes."""
    per = 4 // jnp.dtype(dtype).itemsize
    if width % (128 * per):
        raise ValueError(f"a row of {width} {jnp.dtype(dtype).name} is not "
                         f"a whole number of 128 words")
    return width // per


def pack_rows(x):
    """``x`` ``[..., width]`` (bfloat16 or float32) as ``uint32`` ``[...,
    words]``: bfloat16 numbers ``i`` and ``words + i`` in the low and high
    half of word ``i``; a float32 number's own bits."""
    if x.dtype == jnp.float32:
        return jax.lax.bitcast_convert_type(x, jnp.uint32)
    if x.dtype != jnp.bfloat16:
        raise ValueError(f"rows of {x.dtype}: bfloat16 or float32")
    h = x.shape[-1] // 2
    b = jax.lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.uint32)
    return b[..., :h] | (b[..., h:] << 16)


def unpack_rows(u, dtype):
    """The parts of rows ``u`` ``[..., words]``, in order, each ``[...,
    words]`` of ``dtype``: the low and high halves (bfloat16) or the row
    itself (float32). A bfloat16 number put in a float32's high half is
    that float32 exactly, so the halves come out as they went in."""
    if jnp.dtype(dtype) == jnp.float32:
        return [jax.lax.bitcast_convert_type(u, jnp.float32)]
    lo = jax.lax.bitcast_convert_type(u << 16, jnp.float32)
    hi = jax.lax.bitcast_convert_type(u & jnp.uint32(0xFFFF0000),
                                      jnp.float32)
    return [lo.astype(dtype), hi.astype(dtype)]


def _split(q, parts):
    """A head row ``[..., width]`` cut as its row's parts are."""
    w = q.shape[-1] // parts
    return [q[..., i * w:(i + 1) * w] for i in range(parts)]


def _scores(q, parts):
    """``q`` ``[M, width]`` against the rows' parts ``[K, words]``: the
    sum of the parts' products, float32 ``[M, K]``."""
    s = None
    for qi, ri in zip(_split(q, len(parts)), parts):
        t = jax.lax.dot_general(qi, ri, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = t if s is None else s + t
    return s


def _weighted(p, parts, dc):
    """``p`` ``[M, K]`` against the rows' first ``dc`` numbers: float32
    ``[M, dc]``."""
    outs, left = [], dc
    for ri in parts:
        if left <= 0:
            break
        take = min(left, ri.shape[-1])
        outs.append(jnp.dot(p.astype(ri.dtype), ri[:, :take],
                            preferred_element_type=jnp.float32))
        left -= take
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=-1)


def _write_kernel(rows_ref, ly_ref, new_hbm, pages_in, pages_out, sem, *,
                  page_size, n):
    del pages_in                      # aliased to pages_out

    def start(i, c):
        r = rows_ref[i]
        pltpu.make_async_copy(new_hbm.at[i], pages_out.at[
            ly_ref[0], r // page_size, r % page_size], sem.at[0]).start()
        return c

    def wait(i, c):
        pltpu.make_async_copy(new_hbm.at[0], pages_out.at[0, 0, 0],
                              sem.at[0]).wait()
        return c

    jax.lax.fori_loop(0, n, start, 0)
    jax.lax.fori_loop(0, n, wait, 0)


def write_rows(pages, layer, rows, new, name="latent_row_write",
               use_kernel=True):
    """``new`` ``[n, words]`` (``uint32``, :func:`pack_rows`) into token
    rows ``rows`` ``[n]`` (``page * page_size + slot``) of ``layer`` of
    ``pages`` ``[L, P, page, 1, words]``, in place: a DMA a row. XLA's
    scatter would first lay a pool of rows out in tiles of 8 rows and back,
    a copy of the whole pool."""
    if not use_kernel:
        return write_rows_reference(pages, layer, rows, new)
    n = new.shape[0]
    ps = pages.shape[2]
    interpret = _interpret()
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    with x64_off(interpret):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA((1,))])
        return pl.pallas_call(
            functools.partial(_write_kernel, page_size=ps, n=n),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(pages.shape, pages.dtype),
            input_output_aliases={3: 0},
            interpret=interpret,
            name=name,
        )(rows.astype(jnp.int32), layer, new[:, None, :], pages)


def write_rows_reference(pages, layer, rows, new):
    L, P, ps, _, H = pages.shape
    return pages.reshape(L, P * ps, H).at[layer, rows].set(new).reshape(
        pages.shape)


def _rows_at(pages, layer, page_table, positions):
    """The packed rows of ``positions`` ``[R, K]`` of each row of
    ``page_table`` ``[R, pages]``: ``[R, K, words]`` (the references)."""
    L, P, ps, _, H = pages.shape
    page = jnp.take_along_axis(page_table, positions // ps, axis=-1,
                               mode="clip")
    flat = (jnp.asarray(layer, jnp.int32) * P + page) * ps + positions % ps
    return jnp.take(pages.reshape(L * P * ps, H), flat, axis=0, mode="clip")


# --------------------------------------------------------------------------
# a decode tick's attention over each query's selected rows
# --------------------------------------------------------------------------

def _attn_kernel(pt_ref, cnt_ref, ly_ref, pos_hbm, q_ref, pages_hbm, o_ref,
                 pos_smem, buf, sem, pos_sem, *, page_size, scale, dc, k,
                 dtype):
    n, last = pl.program_id(0), pl.num_programs(0) - 1

    def fetch(m, slot):
        """Query ``m``'s selected rows into ``buf[slot]``: its positions
        into scalar memory, then a DMA a row through the page table."""
        cp = pltpu.make_async_copy(pos_hbm.at[m], pos_smem.at[slot],
                                   pos_sem.at[0])
        cp.start()
        cp.wait()

        def row(i, c):
            s = pos_smem[slot, 0, i]
            pltpu.make_async_copy(
                pages_hbm.at[ly_ref[0], pt_ref[m, s // page_size],
                             s % page_size],
                buf.at[slot, i], sem.at[slot]).start()
            return c

        jax.lax.fori_loop(0, cnt_ref[m], row, 0)

    @pl.when(n == 0)
    def _():
        fetch(0, 0)

    slot = n % 2

    @pl.when(n < last)
    def _():
        fetch(n + 1, 1 - slot)

    def wait(i, c):
        pltpu.make_async_copy(pages_hbm.at[0, 0, 0], buf.at[slot, 0],
                              sem.at[slot]).wait()
        return c

    jax.lax.fori_loop(0, cnt_ref[n], wait, 0)
    cnt = cnt_ref[n]
    # the rows past the selection hold whatever an earlier query left
    live = jax.lax.broadcasted_iota(jnp.int32, (k, 1), 0) < cnt
    parts = unpack_rows(jnp.where(live, buf[slot][:, 0, :],
                                   jnp.uint32(0)), dtype)
    s = _scores(q_ref[...], parts) * jnp.float32(scale)
    s = jnp.where(jax.lax.broadcasted_iota(jnp.int32, (1, k), 1) < cnt, s,
                  _NEG)
    m = jnp.max(s, axis=1, keepdims=True)
    p = jnp.exp(s - m)
    o_ref[...] = _weighted(p, parts, dc) / jnp.sum(p, axis=1, keepdims=True)


def sparse_mla(q, pages, layer, page_table, positions, counts, scale, dc,
               name="sparse_mla", use_kernel=True):
    """``o_lat`` ``[N, nh, dc]`` float32 of the absorbed head rows ``q``
    ``[N, nh, width]`` over the rows of ``pages`` ``[L, P, page, 1,
    words]`` at ``layer`` that each query selected: the first ``counts``
    ``[N]`` of its ``positions`` ``[N, K]``, looked up through its row of
    ``page_table`` ``[N, pages]``. The rows are ``q``'s type."""
    if not use_kernel:
        return sparse_mla_reference(q, pages, layer, page_table, positions,
                                    counts, scale, dc)
    N, nh, W = q.shape
    H = pages.shape[-1]
    K = positions.shape[1]
    interpret = _interpret()
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    parts = W // H
    vmem = 2 * K * H * 4 + K * H * 4 * (1 + parts) + 2 * nh * W * 2 \
        + 3 * nh * K * 4 + 2 * nh * dc * 4
    with x64_off(interpret):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(N,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec((None, nh, W),
                                   lambda n, pt, c, ly: (n, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, nh, dc),
                                   lambda n, pt, c, ly: (n, 0, 0)),
            scratch_shapes=[pltpu.SMEM((2, 1, K), jnp.int32),
                            pltpu.VMEM((2, K, 1, H), jnp.uint32),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SemaphoreType.DMA((1,))])
        return pl.pallas_call(
            functools.partial(_attn_kernel, page_size=pages.shape[2],
                              scale=float(scale), dc=dc, k=K, dtype=q.dtype),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((N, nh, dc), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                # a step fetches the next query's rows: steps in order
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=max(int(vmem * 3 // 2), 16 << 20)),
            interpret=interpret,
            name=name,
        )(page_table.astype(jnp.int32), counts.astype(jnp.int32), layer,
          positions.astype(jnp.int32)[:, None, :], q, pages)


def sparse_mla_reference(q, pages, layer, page_table, positions, counts,
                         scale, dc):
    """:func:`sparse_mla` in plain XLA: the rows gathered (those past a
    query's count as zeros, as the kernel holds them), then a masked
    softmax."""
    ok = jnp.arange(positions.shape[1])[None] < counts[:, None]
    rows = jnp.concatenate(unpack_rows(jnp.where(
        ok[..., None], _rows_at(pages, layer, page_table, positions),
        jnp.uint32(0)), q.dtype), -1)
    s = jnp.einsum("nhw,nkw->nhk", q, rows,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(ok[:, None, :], s, _NEG)
    p = jax.nn.softmax(s, -1)
    return jnp.einsum("nhk,nkc->nhc", p.astype(rows.dtype), rows[..., :dc],
                      preferred_element_type=jnp.float32)


# --------------------------------------------------------------------------
# a chunk's attention over its pages, each query's selection a mask
# --------------------------------------------------------------------------

def _masked_kernel(pt_ref, off_ref, ly_ref, q_ref, k_ref, b_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, page_size, scale, dc, tq, nh,
                   dtype):
    i, j = pl.program_id(0), pl.program_id(1)
    last = (off_ref[0] + (i + 1) * tq - 1) // page_size

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(j <= last)
    def _():
        parts = unpack_rows(k_ref[...], dtype)           # [page, words]
        s = _scores(q_ref[...], parts)
        bias = b_ref[...].astype(jnp.float32)            # [tq, page]
        s = (s * jnp.float32(scale)).reshape(tq, nh, page_size) \
            + bias[:, None, :]
        s = s.reshape(tq * nh, page_size)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        m_scr[...] = m_new
        l_scr[...] = corr * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = corr * acc_scr[...] + _weighted(p, parts, dc)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)


def masked_mla_chunk(q, pages, layer, page_table, q_offset, bias, scale, dc,
                     tq=16, name="sparse_mla_prefill", use_kernel=True):
    """``o_lat`` ``[C, nh, dc]`` float32 of a chunk's absorbed head rows
    ``q`` ``[C, nh, width]`` (positions ``q_offset + i``) over the latent
    rows of its sequence's pages (``pages`` ``[L, P, page, 1, words]`` at
    ``layer``, ``page_table`` ``[1, pages]``; the rows are ``q``'s type),
    each query's selection given as an additive ``bias`` ``[C, pages *
    page]`` (0 where selected, a large negative number elsewhere; a
    selection holds ``s <= t`` only). A grid of (query tile, page): a
    tile's pages past its last position are not read, and a page's rows
    serve all of the tile's queries in one product, the selection a mask
    on the scores."""
    if not use_kernel:
        return masked_mla_chunk_reference(q, pages, layer, page_table,
                                          bias, scale, dc)
    C, nh, W = q.shape
    L, P, ps, _, H = pages.shape
    if C % tq:
        raise ValueError(f"{C} queries in tiles of {tq}")
    interpret = _interpret()
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    off = jnp.reshape(jnp.asarray(q_offset, jnp.int32), (1,))

    def clamp(i, j, off):
        return jnp.minimum(j, (off[0] + (i + 1) * tq - 1) // ps)

    vmem = 2 * tq * nh * W * 2 + 2 * ps * H * 4 + 2 * ps * W * 2 \
        + 2 * tq * ps * 2 + 2 * tq * nh * dc * 4 \
        + tq * nh * (dc + 2 * 128) * 4 + 3 * tq * nh * ps * 4
    with x64_off(interpret):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(C // tq, page_table.shape[1]),
            in_specs=[
                pl.BlockSpec((tq * nh, W), lambda i, j, pt, off, ly: (i, 0)),
                pl.BlockSpec((None, None, ps, None, H),
                             lambda i, j, pt, off, ly:
                             (ly[0], pt[0, clamp(i, j, off)], 0, 0, 0)),
                pl.BlockSpec((tq, ps), lambda i, j, pt, off, ly:
                             (i, clamp(i, j, off))),
            ],
            out_specs=pl.BlockSpec((tq * nh, dc),
                                   lambda i, j, pt, off, ly: (i, 0)),
            scratch_shapes=[pltpu.VMEM((tq * nh, 1), jnp.float32),
                            pltpu.VMEM((tq * nh, 1), jnp.float32),
                            pltpu.VMEM((tq * nh, dc), jnp.float32)],
        )
        out = pl.pallas_call(
            functools.partial(_masked_kernel, page_size=ps,
                              scale=float(scale), dc=dc, tq=tq, nh=nh,
                              dtype=q.dtype),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((C * nh, dc), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=max(int(vmem * 3 // 2), 16 << 20)),
            interpret=interpret,
            name=name,
        )(page_table.astype(jnp.int32), off, layer, q.reshape(C * nh, W),
          pages, bias.astype(jnp.bfloat16))
    return out.reshape(C, nh, dc)


def masked_mla_chunk_reference(q, pages, layer, page_table, bias, scale,
                               dc):
    """:func:`masked_mla_chunk` in plain XLA: the sequence's rows cut out
    through the table, then a masked softmax."""
    L, P, ps, _, H = pages.shape
    packed = jax.lax.dynamic_index_in_dim(
        pages, jnp.asarray(layer, jnp.int32), 0, keepdims=False)[
        page_table[0]].reshape(-1, H)
    rows = jnp.concatenate(unpack_rows(packed, q.dtype), -1)
    s = jnp.einsum("chw,sw->chs", q, rows,
                   preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(s + bias.astype(jnp.float32)[:, None, :], -1)
    return jnp.einsum("chs,sd->chd", p.astype(rows.dtype), rows[:, :dc],
                      preferred_element_type=jnp.float32)
