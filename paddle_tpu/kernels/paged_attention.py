"""Ragged paged-attention decode kernel (Pallas TPU).

The serving-side counterpart of :mod:`.flash_attention`: one query token
per sequence attends over that sequence's KV cache stored as fixed-size
HBM *pages* (PAPERS.md "Ragged Paged Attention"). Live HBM tracks actual
tokens instead of ``max_position_embeddings`` — the page pool
(:mod:`paddle_tpu.serving.kv_pool`) hands each sequence a page table and
this kernel gathers exactly those pages.

Layout contract:

- ``q``        ``[B, num_heads, d]`` — the new token's projected queries.
- ``k_pages``/``v_pages`` ``[num_layers, num_pages, page_size,
  num_kv_heads, d]`` — the WHOLE pool, with ``layer`` (a traced int32
  scalar) saying which layer's pages this call reads. A rank-4
  ``[num_pages, page_size, num_kv_heads, d]`` array is the same call on
  a pool of one layer read at layer 0 (a leading axis of one is a
  bitcast): one path, chosen by the rank of the input. Page 0 is the
  pool's reserved *sink* page (padding page-table entries point at it;
  it is never read unmasked).
- ``page_table`` ``[B, pages_per_seq]`` int32 — entry ``j`` is the HBM
  page holding tokens ``[j*page_size, (j+1)*page_size)`` of sequence
  ``b``; entries beyond the sequence's pages are sink references.
- ``seq_lens`` ``[B]`` int32 — true token count per sequence INCLUDING
  the token being decoded (its K/V must already be written to its page).
  A zero length marks an idle batch slot: every key is masked and the
  (finite, garbage) output row is discarded by the caller.

Grid: one step per ``(sequence, kv_page)`` — the page table AND the
layer index ride :class:`pltpu.PrefetchScalarGridSpec` scalar prefetch so
the ``k_pages`` BlockSpec index_map ``(layer, page_table[b, j], 0, 0, 0)``
can gather the right HBM page of the right layer into VMEM while the
online-softmax state (m/l/acc) lives in VMEM scratch, exactly the
flash-attention streaming scheme but with an indirection per block.
Fully-padded pages (``j*page_size >= seq_len``) early-out. Because the
layer is picked by the block's index and not by a slice, the serving
programs carry the whole (donated, aliased) pool through their layer
loop and never cut a layer's pages out of it.

A block holds ALL KV heads of one page of one layer, ``(layer squeezed,
1, page_size, nkv, d)``: the TPU lowering wants a block's last two dims
to be multiples of (8, 128) or the array's own, and one head out of
``nkv`` is neither. The heads are walked inside the kernel body. (A flat
``[pages, page, nkv*d]`` view of the pool would give lane-aligned head
slices, but on the chip that reshape is a relayout copy of the whole
pool per call, not a bitcast.)

A **wide group** (queries a KV head a multiple of 16: a block engine
hands a KV head ``block x group`` = 32 of them) takes
:func:`_decode_kernel_grouped`: the same grid, page walk and length
mask, but each KV head's ``[g, d]`` queries meet the page's keys in one
MXU product, q and the output head-major; the trace calls it
``paged_attention_decode_grouped``. Walked one query at a time on the VPU
those 32 read 1.7% of the kernel's roofline on the chip (PR 30); a group
of one (GPT) is untouched and keeps the name ``paged_attention_decode``.

On CPU the kernels run in interpreter mode so tier-1 asserts
paged-decode == XLA reference attention without a TPU; the same
``pallas_call`` compiles for the chip (x64 off around the trace;
``tests/test_chip_compile.py`` asks the v5e compiler at 345M and 1.3B
widths, rank-4 and layer-indexed rank-5 pools).

**Shared (prefix-cache) pages**: all reads here are page-table gathers,
so a page mapped into many sequences' tables (refcounted sharing in
``serving.kv_pool`` / ``serving.prefix_cache``) is attended with zero
copies; writes never go through this module — the pool's copy-on-write
barrier keeps every written page exclusive. The chunk/suffix prefill
read path is :func:`paged_prefill_attention` (traced ``q_offset``
causal rule, one program for every chunk position).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._mosaic import x64_off

_NEG_INF = -1e30

_ARB2 = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


# scoped VMEM a kernel may ask for without saying so (v5e)
_VMEM_DEFAULT = 16 << 20


def _prefill_params(nh, Cp, d, dtype):
    """The chunk kernel's compiler parameters: the default ones while its
    scratch (three f32 ``[nh, Cp, .]`` of a lane tile or ``d``) and its
    double-buffered q and out blocks fit the default scoped VMEM, as at
    16 heads of a 256-row chunk; a stated limit with room where they do
    not, as at 32."""
    need = nh * Cp * (2 * 128 + max(d, 128)) * 4 \
        + 4 * nh * Cp * d * jnp.dtype(dtype).itemsize
    if need <= _VMEM_DEFAULT * 3 // 4:
        return _ARB2
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=int(need * 3 // 2))


def _layer_index(k_pages, layer):
    """``layer`` as an int32 scalar, checked against the pool's rank: a
    rank-4 pool is a pool of one layer, read at layer 0."""
    if (k_pages.ndim == 5) == (layer is None):
        raise ValueError(
            f"a rank-5 pool needs the `layer` to read and a rank-4 pool "
            f"holds just one: got rank {k_pages.ndim}, layer={layer!r}")
    return jnp.asarray(0 if layer is None else layer, jnp.int32)


def _check_widths(q, k_pages, v_pages):
    """q, k and v rows are of one width here: the kernels' blocks, their
    scores' lane reduce and their accumulators all take the pool's ``d``.
    A caller whose score rows are narrower than its value rows (two score
    heads of ``d / 2`` onto value rows of ``d``) lays its rows out at one
    width itself: a pair of heads one head of the pool, and each query of
    a pair a row of its own filled with zeros where the other's lanes are,
    ``[q1 ; 0]`` and ``[0 ; q2]``."""
    if not q.shape[-1] == k_pages.shape[-1] == v_pages.shape[-1] \
            or k_pages.shape != v_pages.shape:
        raise ValueError(
            f"q rows of width {q.shape[-1]} against a pool of k "
            f"{k_pages.shape} and v {v_pages.shape}: the paged kernels "
            f"take q, k and v rows of one width (score rows narrower than "
            f"the value rows go in as a pair of heads a pool row, with "
            f"each query zero-filled to the pair's width)")


def _pool_and_layer(k_pages, v_pages, layer):
    """The kernels' one view of a pool: rank 5 ``[L, P, ps, nkv, d]``
    and the layer as a ``(1,)`` int32 for the scalar prefetch. ``L`` is
    the layers the pool holds, which a model may have more of (one full
    attention layer of 32 writes the pool that eight layers read)."""
    layer = jnp.reshape(_layer_index(k_pages, layer), (1,))
    if k_pages.ndim == 4:
        k_pages, v_pages = k_pages[None], v_pages[None]
    return k_pages, v_pages, layer


def _layer_pages(k_pages, v_pages, layer):
    """The XLA paths' view: one layer's pages ``[P, ps, nkv, d]``."""
    layer = _layer_index(k_pages, layer)
    if k_pages.ndim == 4:
        return k_pages, v_pages
    return (jax.lax.dynamic_index_in_dim(k_pages, layer, 0, keepdims=False),
            jax.lax.dynamic_index_in_dim(v_pages, layer, 0, keepdims=False))


def _decode_kernel(pt_ref, sl_ref, ly_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, page_size, g, scale):
    """One (sequence b, page j) step of the online softmax over every
    head at once; scratch carries the running (max, denom, weighted-V)
    state across the page walk. Decode is bound by the bytes of K/V,
    not by the MXU: scores are a multiply and a lane reduce over ``d``
    with the reduced axis kept — tokens stay on the major axis, heads on
    sublanes, ``d`` on lanes throughout, so there is no one-row dot, no
    transpose and no relayout."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    npg = pl.num_programs(1)

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    sl = sl_ref[b]
    # ragged early-out: pages wholly beyond this sequence's length
    # (incl. every page of an idle slot, sl == 0) are skipped
    run = j * np.int32(page_size) < sl

    @pl.when(run)
    def _():
        k = k_ref[0].astype(jnp.float32)               # [page, nkv, d]
        v = v_ref[0].astype(jnp.float32)
        live = j * np.int32(page_size) + jax.lax.broadcasted_iota(
            jnp.int32, (page_size, 1, 1), 0) < sl
        for r in range(g):          # query r of each kv head's group
            q = q_ref[0, r].astype(jnp.float32)        # [nkv, d]
            s = jnp.sum(k * q[None], axis=2, keepdims=True) \
                * jnp.float32(scale)                   # [page, nkv, 1]
            s = jnp.where(live, s, jnp.float32(_NEG_INF))
            m_prev = m_scr[r]                          # [nkv, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
            p = jnp.exp(s - m_new[None])               # [page, nkv, 1]
            corr = jnp.exp(m_prev - m_new)
            m_scr[r] = m_new
            l_scr[r] = corr * l_scr[r] + jnp.sum(p, axis=0)
            acc_scr[r] = corr * acc_scr[r] + jnp.sum(p * v, axis=0)

    @pl.when(j == npg - 1)
    def _():
        # idle slots never ran: l == 0 → emit finite garbage, not NaN
        l = jnp.maximum(l_scr[...], jnp.float32(1e-30))
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def _decode_kernel_grouped(pt_ref, sl_ref, ly_ref, q_ref, k_ref, v_ref,
                           o_ref, m_scr, l_scr, acc_scr, *, page_size,
                           scale):
    """:func:`_decode_kernel` for wide groups (a block engine hands a KV
    head ``block x group`` queries): one (sequence b, page j) step, KV
    head by KV head on the MXU: the head's ``[g, d]`` queries against
    the page's ``[page, d]`` keys, as the chunk kernel does with its
    rows. ``q`` and the output are head-major, ``[nkv, g, d]`` a
    sequence."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    npg = pl.num_programs(1)
    nkv = q_ref.shape[1]

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    sl = sl_ref[b]

    @pl.when(j * np.int32(page_size) < sl)
    def _():
        live = j * np.int32(page_size) + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1) < sl
        for h in range(nkv):
            q = q_ref[0, h]                # [g, d]
            k = k_ref[0, :, h, :]          # [page_size, d]
            v = v_ref[0, :, h, :]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) \
                * jnp.float32(scale)       # [g, page_size]
            s = jnp.where(live, s, jnp.float32(_NEG_INF))
            m_prev = m_scr[h]              # [g, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            m_scr[h] = m_new
            l_scr[h] = corr * l_scr[h] + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[h] = corr * acc_scr[h] + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    @pl.when(j == npg - 1)
    def _():
        for h in range(nkv):
            l = jnp.maximum(l_scr[h], jnp.float32(1e-30))
            o_ref[0, h] = (acc_scr[h] / l).astype(o_ref.dtype)


# groups at least this wide (and a multiple of it: whole bf16 sublane
# tiles) go through the MXU, head by head; narrower ones stay on the VPU
_GROUP_ON_MXU = 16


def paged_attention_decode(q, k_pages, v_pages, page_table, seq_lens,
                           scale=None, layer=None):
    """Single-token decode attention over a paged KV cache.

    ``q`` ``[B, num_heads, d]``; pages ``[num_layers, num_pages,
    page_size, num_kv_heads, d]`` with ``layer`` the (traced) layer to
    read, or rank 4 without it — one layer (num_kv_heads may divide
    num_heads — MQA/GQA: query heads ``[h*g, (h+1)*g)`` read kv head
    ``h``); ``page_table`` ``[B, pages_per_seq]`` int32; ``seq_lens``
    ``[B]`` int32 true lengths (0 = idle slot). Returns
    ``[B, num_heads, d]``.
    """
    B, nh, d = q.shape
    _check_widths(q, k_pages, v_pages)
    k_pages, v_pages, layer = _pool_and_layer(k_pages, v_pages, layer)
    _, _, page_size, nkv, _ = k_pages.shape
    if nh % nkv:
        raise ValueError(f"num_heads {nh} must be a multiple of "
                         f"num_kv_heads {nkv}")
    g = nh // nkv
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    interpret = _interpret()
    grouped = g % _GROUP_ON_MXU == 0
    with x64_off(interpret):
        # q rides as [B, g, nkv, d]: row r of a block holds the r-th
        # query of every kv head's group, aligned with a page's heads
        # (head-major [B, nkv, g, d] for a wide group: a [g, d] matrix
        # a KV head for the MXU)
        q_shape = (nkv, g) if grouped else (g, nkv)
        q_block = pl.BlockSpec((1,) + q_shape + (d,),
                               lambda b, j, pt, sl, ly: (b, 0, 0, 0))
        # the paged gather: the layer and the page table pick which HBM
        # page this grid step DMAs into VMEM (the layer axis squeezed)
        kv_block = pl.BlockSpec(
            (None, 1, page_size, nkv, d),
            lambda b, j, pt, sl, ly: (ly[0], pt[b, j], 0, 0, 0))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, page_table.shape[1]),
            in_specs=[q_block, kv_block, kv_block],
            out_specs=q_block,
            scratch_shapes=[
                pltpu.VMEM(q_shape + (1,), jnp.float32),
                pltpu.VMEM(q_shape + (1,), jnp.float32),
                pltpu.VMEM(q_shape + (d,), jnp.float32),
            ],
        )
        kernel = functools.partial(
            _decode_kernel_grouped, page_size=page_size,
            scale=float(scale)) if grouped else functools.partial(
            _decode_kernel, page_size=page_size, g=g, scale=float(scale))
        q = q.reshape(B, nkv, g, d)
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B,) + q_shape + (d,), q.dtype),
            compiler_params=_ARB2,
            interpret=interpret,
            # its own name in the trace, so that a reader can tell which
            # body ran
            name="paged_attention_decode_grouped" if grouped
            else "paged_attention_decode",
        )(page_table.astype(jnp.int32), seq_lens.astype(jnp.int32), layer,
          q if grouped else q.swapaxes(1, 2), k_pages, v_pages)
    return (out if grouped else out.swapaxes(1, 2)).reshape(B, nh, d)


def _decode_kernel_rows(pt_ref, sl_ref, ly_ref, q_ref, k_ref, v_ref, o_ref,
                        m_scr, l_scr, acc_scr, *, page_size, scale):
    """:func:`_decode_kernel_grouped` over a pool of **rows**: a page is
    ``[page, nkv * d]``, a token's KV heads side by side on the lanes, so
    a head's keys are a lane-aligned slice of a dense tile where the
    ``[page, nkv, d]`` block hands the MXU a sublane gather (a head's row
    out of every token's padded tile: at two heads of 640 that gather,
    not the bytes, was the kernel's time on the chip). ``q`` and the
    output are head-major, ``[nkv, g, d]`` a sequence."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    npg = pl.num_programs(1)
    nkv, _, d = q_ref.shape[1:]

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    sl = sl_ref[b]

    @pl.when(j * np.int32(page_size) < sl)
    def _():
        live = j * np.int32(page_size) + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1) < sl
        # three passes over the heads, not one: every head's scores, then
        # every head's softmax step, then every head's weighted values.
        # The products of a pass do not wait for one another, so the MXUs
        # work side by side (head by head, scores-softmax-values in a
        # chain, the same kernel took 1.5 to 1.8 x as long on the chip)
        scores = [jax.lax.dot_general(
            q_ref[0, h], k_ref[0, :, h * d:(h + 1) * d],
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            * jnp.float32(scale) for h in range(nkv)]   # [g, page_size]
        weights = []
        for h, s in enumerate(scores):
            s = jnp.where(live, s, jnp.float32(_NEG_INF))
            m_prev = m_scr[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            m_scr[h] = m_new
            l_scr[h] = corr * l_scr[h] + jnp.sum(p, axis=1, keepdims=True)
            weights.append((p, corr))
        for h, (p, corr) in enumerate(weights):
            v = v_ref[0, :, h * d:(h + 1) * d]          # [page_size, d]
            acc_scr[h] = corr * acc_scr[h] + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    @pl.when(j == npg - 1)
    def _():
        for h in range(nkv):
            l = jnp.maximum(l_scr[h], jnp.float32(1e-30))
            o_ref[0, h] = (acc_scr[h] / l).astype(o_ref.dtype)


def paged_attention_decode_rows(q, k_rows, v_rows, page_table, seq_lens,
                                scale=None, layer=0, name=None,
                                use_kernel=True):
    """:func:`paged_attention_decode` over a pool of rows.

    ``k_rows``/``v_rows`` ``[num_layers, num_pages, page_size, nkv * d]``
    (a token's KV heads side by side: what a model holds whose KV heads
    are not a count the chip tiles without padding, ten of 128 say; a
    dense ``[page, nkv * d]`` tile a page) read at ``layer``; ``q`` ``[B,
    nkv, g, d]`` head-major, ``g`` query rows a KV head (a multiple of 16
    on the chip: whole bf16 sublane tiles for the MXU; fill with zero
    rows). ``page_table``, ``seq_lens`` and ``scale`` as there; ``name``
    (static) is the call's name in the trace, for a program that reads
    more than one cache through this kernel and a reader that has to
    tell them apart. Returns ``[B, nkv, g, d]`` in **float32**, the
    accumulator as it stands: the caller this body serves subtracts one
    head's output from another's, and two nearly equal rows rounded to
    the served type first lose what their difference keeps.
    ``use_kernel=False`` is the XLA reference (the pool's rows cut into
    heads, then :func:`paged_attention_reference`)."""
    B, nkv, g, d = q.shape
    L, _, page_size, width = k_rows.shape
    if width != nkv * d or k_rows.shape != v_rows.shape:
        raise ValueError(
            f"q of {nkv} heads of {d} against pool rows of k {k_rows.shape} "
            f"and v {v_rows.shape}: a row holds the KV heads side by side, "
            f"q, k and v rows of one width a head")
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if not use_kernel:
        heads = lambda a: a.reshape(*a.shape[:3], nkv, d)
        out = paged_attention_reference(
            q.reshape(B, nkv * g, d).astype(jnp.float32), heads(k_rows),
            heads(v_rows), page_table, seq_lens, scale=scale, layer=layer)
        return out.reshape(q.shape)
    interpret = _interpret()
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    with x64_off(interpret):
        q_block = pl.BlockSpec((1, nkv, g, d),
                               lambda b, j, pt, sl, ly: (b, 0, 0, 0))
        kv_block = pl.BlockSpec(
            (None, 1, page_size, width),
            lambda b, j, pt, sl, ly: (ly[0], pt[b, j], 0, 0))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, page_table.shape[1]),
            in_specs=[q_block, kv_block, kv_block],
            out_specs=q_block,
            scratch_shapes=[
                pltpu.VMEM((nkv, g, 1), jnp.float32),
                pltpu.VMEM((nkv, g, 1), jnp.float32),
                pltpu.VMEM((nkv, g, d), jnp.float32),
            ],
        )
        return pl.pallas_call(
            functools.partial(_decode_kernel_rows, page_size=page_size,
                              scale=float(scale)),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(q.shape, jnp.float32),
            compiler_params=_ARB2,
            interpret=interpret,
            name=name or "paged_attention_decode_rows",
        )(page_table.astype(jnp.int32), seq_lens.astype(jnp.int32), layer,
          q, k_rows, v_rows)


def _prefill_kernel(pt_ref, off_ref, ly_ref, q_ref, k_ref, v_ref, o_ref,
                    m_scr, l_scr, acc_scr, *, page_size, scale, g, block):
    """One (sequence b, page j) step of the ragged chunk prefill: a whole
    C-row chunk attends one paged KV block per step, head by head (query
    head ``h`` reads KV head ``h // g``), online-softmax state in VMEM
    scratch, the causal rule applied with the TRACED chunk offset (row
    ``off + i`` sees cols ``<= off + i``; with ``block`` > 1 a column is
    seen if its block of that many positions is not later than the
    row's)."""
    j = pl.program_id(1)
    npg = pl.num_programs(1)
    off = off_ref[0]
    _, C, nh, _ = q_ref.shape

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # ragged early-out: pages wholly past the last chunk row's position
    # (col_start > off + C - 1; its block's last position under the
    # block rule) are fully masked — skip them
    last = off + np.int32(C - 1)
    if block > 1:
        last = last // np.int32(block) * np.int32(block) \
            + np.int32(block - 1)
    run = j * np.int32(page_size) <= last

    @pl.when(run)
    def _():
        row = off + jax.lax.broadcasted_iota(jnp.int32, (C, page_size), 0)
        col = j * np.int32(page_size) + jax.lax.broadcasted_iota(
            jnp.int32, (C, page_size), 1)
        seen = col <= row if block == 1 \
            else col // np.int32(block) <= row // np.int32(block)
        for h in range(nh):
            q = q_ref[0, :, h, :]          # [C, d]
            k = k_ref[0, :, h // g, :]     # [page_size, d]
            v = v_ref[0, :, h // g, :]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) \
                * jnp.float32(scale)       # [C, page_size]
            s = jnp.where(seen, s, jnp.float32(_NEG_INF))
            m_prev = m_scr[h]              # [C, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            m_scr[h] = m_new
            l_scr[h] = corr * l_scr[h] + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[h] = corr * acc_scr[h] + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    @pl.when(j == npg - 1)
    def _():
        # col 0 is always <= every row (off >= 0), so l > 0 for real
        # rows; padded chunk rows still produce finite garbage
        for h in range(nh):
            l = jnp.maximum(l_scr[h], jnp.float32(1e-30))
            o_ref[0, h] = (acc_scr[h] / l).astype(o_ref.dtype)


def ragged_prefill_attention(q, k_pages, v_pages, page_table, q_offset,
                             scale=None, interpret=None, layer=None,
                             block=1):
    """True ragged Pallas chunk-prefill attention over a paged KV cache.

    Fused form of :func:`paged_prefill_attention` (same signature, same
    numerics; pool and ``layer`` as in :func:`paged_attention_decode`):
    instead of the dense page gather (``k_pages[page_table]``
    materializes every sequence's KV twice), the page table and the
    layer ride :class:`pltpu.PrefetchScalarGridSpec` scalar prefetch —
    exactly the decode kernel's scheme — and each grid step DMAs one
    page into VMEM while online-softmax state (m/l/acc per chunk row)
    lives in scratch. The causal rule uses the **traced** ``q_offset``,
    so one compiled program covers every chunk position.

    The engine's chunk program calls it on the whole pool; it is also
    the target template of the ``ragged_prefill`` auto-fusion rewrite
    rule (:mod:`paddle_tpu.analysis.rewrite`), which swaps it in for a
    dense gather over one layer's pages. The ``pallas_call`` is named
    ``autofuse_ragged_prefill`` so the cost pass recognizes rewritten
    programs (PTCS005). ``num_kv_heads`` may divide ``num_heads``
    (MQA/GQA, as in the decode kernel). ``block`` (static) widens the
    causal rule to blocks of that many positions: row ``i`` sees column
    ``j`` iff ``j // block <= i // block``; 1 is the plain causal rule.
    """
    B, C, nh, d = q.shape
    _check_widths(q, k_pages, v_pages)
    k_pages, v_pages, layer = _pool_and_layer(k_pages, v_pages, layer)
    _, _, ps, nkv, _ = k_pages.shape
    if nh % nkv:
        raise ValueError(f"num_heads {nh} must be a multiple of "
                         f"num_kv_heads {nkv}")
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if interpret is None:
        interpret = _interpret()
    # Mosaic tiling: chunk rows to the 8-sublane multiple (interpret
    # mode skips the pad)
    Cp = C if interpret else -(-C // 8) * 8
    with x64_off(interpret):
        if Cp != C:
            q = jnp.pad(q, [(0, 0), (0, Cp - C), (0, 0), (0, 0)])
        q_block = pl.BlockSpec((1, Cp, nh, d),
                               lambda b, j, pt, off, ly: (b, 0, 0, 0))
        kv_block = pl.BlockSpec(
            (None, 1, ps, nkv, d),
            lambda b, j, pt, off, ly: (ly[0], pt[b, j], 0, 0, 0))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, page_table.shape[1]),
            in_specs=[q_block, kv_block, kv_block],
            # head-major out: a whole [Cp, d] store per head (Mosaic
            # refuses the strided bf16 store into [Cp, nh, d] at d < 128)
            out_specs=pl.BlockSpec((1, nh, Cp, d),
                                   lambda b, j, pt, off, ly: (b, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((nh, Cp, 1), jnp.float32),
                pltpu.VMEM((nh, Cp, 1), jnp.float32),
                pltpu.VMEM((nh, Cp, d), jnp.float32),
            ],
        )
        out = pl.pallas_call(
            functools.partial(_prefill_kernel, page_size=ps,
                              scale=float(scale), g=nh // nkv,
                              block=int(block)),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B, nh, Cp, d), q.dtype),
            compiler_params=_prefill_params(nh, Cp, d, q.dtype),
            interpret=interpret,
            name="autofuse_ragged_prefill",
        )(page_table.astype(jnp.int32),
          jnp.reshape(jnp.asarray(q_offset, jnp.int32), (1,)), layer,
          q, k_pages, v_pages)
    return out.swapaxes(1, 2)[:, :C]


def paged_prefill_attention(q, k_pages, v_pages, page_table, q_offset,
                            scale=None, layer=None, block=1):
    """Chunk/suffix prefill attention over a paged KV cache (XLA path).

    ``q`` ``[B, C, num_heads, d]`` — a prompt *chunk* whose row ``i``
    sits at absolute position ``q_offset + i``; pages/table/``layer`` as
    in :func:`paged_attention_decode` (this path cuts the layer's pages
    out of a rank-5 pool itself). Row ``i`` attends keys at positions
    ``<= q_offset + i`` — the flash-attention ``q_offset`` masking rule
    (PR 8), but with a **traced** offset, so ONE compiled program covers
    every chunk position and every cached-prefix length: chunked prefill
    and prefix-cache suffix prefill never recompile (``block``: the
    block rule of :func:`ragged_prefill_attention`). The chunk's own
    K/V must already be scattered into the pages (same contract as
    decode: a position's K/V is written before it is attended).

    Because shared (prefix-cache) pages are read through the same
    gather, a page mapped into many sequences' tables is attended
    without copies; writes stay safe via the pool's copy-on-write
    barrier, never this read path.
    """
    B, C, nh, d = q.shape
    k_pages, v_pages = _layer_pages(k_pages, v_pages, layer)
    _, ps, nkv, _ = k_pages.shape
    g = nh // nkv
    t = page_table.shape[1] * ps
    k = k_pages[page_table].reshape(B, t, nkv, d)
    v = v_pages[page_table].reshape(B, t, nkv, d)
    if g > 1:
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    # mirror gpt_block's dense-attention numerics exactly (divide by
    # sqrt(d) in compute dtype, -1e30 mask, f32 softmax) so chunked
    # prefill is token-for-token equal to the one-shot bucketed prefill
    logits = jnp.einsum("bsnd,btnd->bnst", q, k) / math.sqrt(d) \
        if scale is None else jnp.einsum("bsnd,btnd->bnst", q, k) * scale
    row = jnp.asarray(q_offset, jnp.int32) \
        + jnp.arange(C, dtype=jnp.int32)[:, None]
    col = jnp.arange(t, dtype=jnp.int32)[None, :]
    mask = (col // block <= row // block)[None, None, :, :]
    logits = jnp.where(mask, logits, jnp.asarray(_NEG_INF, logits.dtype))
    probs = jax.nn.softmax(logits.astype(jnp.float32), -1).astype(q.dtype)
    return jnp.einsum("bnst,btnd->bsnd", probs, v)


def paged_attention_reference(q, k_pages, v_pages, page_table, seq_lens,
                              scale=None, layer=None):
    """XLA reference: gather the paged KV dense (one layer's pages, cut
    out of a rank-5 pool at ``layer``), mask to each sequence's true
    length, plain softmax attention. The correctness oracle for the
    kernel and the modelable decode path the static cost pass prices."""
    B, nh, d = q.shape
    k_pages, v_pages = _layer_pages(k_pages, v_pages, layer)
    _, ps, nkv, _ = k_pages.shape
    g = nh // nkv
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    t = page_table.shape[1] * ps
    k = k_pages[page_table].reshape(B, t, nkv, d)
    v = v_pages[page_table].reshape(B, t, nkv, d)
    if g > 1:
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bnd,btnd->bnt", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    mask = (jnp.arange(t, dtype=jnp.int32)[None, None, :]
            < seq_lens.astype(jnp.int32)[:, None, None])
    s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bnt,btnd->bnd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
