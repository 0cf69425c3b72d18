"""Sparse latent serving engine: GLM-5.2 from a latent page pool and an
index-key page array under one page table.

``Glm5ServingEngine`` is the adapter over the paged-engine core
(:mod:`.engine_core`) for :mod:`paddle_tpu.models.glm5`. The
:class:`~.kv_pool.PagePool` holds two arrays of its own under one table:
``k_pages``, a token's latent row ``[c_kv | k_rope | 0]`` of every layer
as 32-bit words (``[L, P, page, 1, row_width / 2]`` in bfloat16: 576
numbers filled to 768, two a word, a row alone on its axis so that a
kernel can fetch one token's row by itself; :mod:`..kernels.sparse_mla`),
and ``aux_pages``, the indexer's key (``[layers with an indexer, P,
page, 128]``) of the layers whose ``indexer_types`` entry is ``full``. A
live token costs ``L * row_width * 2 + F * 128 * 2`` bytes as laid out
(8,192 at the published widths: 5 layers, 2 with an indexer;
``cache_bytes_per_token``) where its numbers are 6,272
(``cache_bytes_needed``). ``block_len`` is 1.

Two programs, both arrays carried, donated and updated in place:

- :func:`glm5_decode_fn`, one per decode bucket. A tick's packed int32
  array gives every bucket row its last token, length and page-table
  row. Each layer writes the row's latent row (and at a ``full`` layer
  its index key), and at a ``full`` layer scores the query against every
  index key of the sequence (``mla_indexer_decode``) and takes the exact
  top ``index_topk`` positions
  (:func:`paddle_tpu.models.glm5.top_positions`: no approximation); a
  ``shared`` layer takes the selection of the ``full`` layer below it,
  inside the program. Attention reads only the selected latent rows:
  ``sparse_mla_decode`` fetches each one through the page table and
  attends in the absorbed form. The expert layer routes over every
  expert and computes the held ones'
  (:func:`paddle_tpu.models.glm5.held_expert_ffn`). New latent rows go
  in by ``latent_row_write``, new index keys by a scatter.
- :func:`glm5_chunk_fn`, one program for every chunk of every prompt
  (batch 1, ``prefill_chunk`` positions): the same layers over the chunk,
  the index score of each of its queries against every index key of the
  sequence so far (``mla_indexer_prefill``), the selection of each as a
  mask over the sequence's positions
  (:func:`paddle_tpu.models.glm5.exact_topk_mask`: the k-th score found
  bit by bit, nothing sorted), and the attention of each query over its
  selected rows (``sparse_mla_prefill``: a tile of queries against a page
  of rows at a time, the mask on the scores); the head at the chunk's
  last real position. On a v5e, gathering each query's 2,048 rows took
  4–7 ms a block of 128 queries and turning the masks into positions
  1.2 s a chunk of 2,048, so the chunk reads its pages whole, in order,
  and scores the rows it did not select at no extra bytes.

Both return the assignments of real tokens to each held expert of each
expert layer after their tokens (a decode tick's ride its readback, a
chunk's are read at the next readback). A decode tick also returns the
positions each layer's attention read for each row (its own selection
at a ``full`` layer, the one below it at a ``shared`` layer; ``-1`` past
a selection) and each expert layer's chosen experts and their gates,
read back only where ``selection_every`` (0: off) asks: at every
position that is a multiple of it, into ``selection_log[seq_id]`` as
``(position, int32[layers, index_topk], int32[expert layers, top-k],
float32[expert layers, top-k])``, which the benchmark holds against the
reference's. A chunk's selections and routing never leave its program.

``prefix_cache=True`` is refused: a hit would map latent and index pages
whose selection history nothing records, and no test covers it yet.

Spans: the core's ``engine.prefill_begin`` and ``engine.prefill_step``
(with ``index_keys``, the keys the chunk's queries score in a layer with
an indexer, and ``selected``, the rows their attention reads a layer);
``engine.decode`` with ``engine.host_prep`` / ``dispatch`` /
``readback`` inside and the attributes ``live_ctx`` (sum of the live
lengths), ``selected`` (sum of ``min(len, index_topk)``: the rows a
layer's attention reads) and ``cache_bytes`` (the bytes of the pages in
use); ``experts.held`` (program ``decode`` or ``chunk``, ``tokens`` the
real rows, ``assigned`` and ``active`` a list over the expert layers:
the assignments to held experts and how many held experts got one), one
a program, recorded when its counts are read.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.sparse_mla import (index_scores, masked_mla_chunk,
                                  pack_rows, row_words, sparse_mla,
                                  write_rows)
from ..models import glm5 as M
from ..profiler.utils import RecordEvent
from .engine_core import EngineShapeError, PagedEngine

__all__ = ["Glm5ServingEngine", "glm5_decode_fn", "glm5_chunk_fn"]

# columns of a decode tick's packed state ``int32[bucket, 2 + pages]``
_TOKEN, _LEN, _TABLE = 0, 1, 2
# a tile of the index score in a chunk: the chunk's queries x heads rows
_INDEX_TILE = 64


def _write(pages, layer, rows, new):
    """``new`` ``[n, W]`` into token rows ``rows`` of ``layer`` of the
    index keys ``[L, P, page, W]`` (a scatter on the ``[L, P * page, W]``
    view: in place on a carried, donated buffer)."""
    L, P, ps, W = pages.shape
    return pages.reshape(L, P * ps, W).at[layer, rows].set(
        new.astype(pages.dtype)).reshape(pages.shape)


def _scale(cfg):
    return cfg.qk_head_dim ** -0.5


def _layers(params, lat, idxp, x, pos, rows, table, limits, valid, real,
            decode, cfg, use_kernel):
    """Every layer over ``x`` ``[N, H]`` at positions ``pos`` ``[N]``,
    whose latent rows land at ``rows``. Decode: ``N`` sequences of one
    query (``table`` ``[N, pages]``, ``limits`` their lengths), each
    selection as positions; a chunk: one sequence of ``N`` queries
    (``table`` ``[1, pages]``), each selection as a mask on the scores.
    ``valid`` ``[N, pages * page]`` is what each query may select, ``real``
    the rows that are tokens. Returns ``(lat, idxp, x, probe, loads)``;
    ``probe`` (decode only) is ``(selections [N, layers, k], experts [N,
    expert layers, top-k], gates [N, expert layers, top-k])``: the
    positions each layer's attention read (``-1`` past a selection), and
    each expert layer's chosen experts and their gates."""
    eps, k = cfg.rms_norm_eps, cfg.index_topk
    selected, chosen, loads, routes = {}, [], [], []
    for l, p in enumerate(params["layers"]):
        a = M.rms_norm(x, p["attn_norm"], eps)
        c_q, q_nope, q_rope, c_kv, k_rope = M.mla_project(p, a, pos, cfg)
        lat = write_rows(lat, l, rows, pack_rows(
            M.latent_row(c_kv, k_rope, cfg)), use_kernel=use_kernel)
        if cfg.indexer_types[l] == "full":
            f = cfg.index_slot(l)
            qi, ki, w = M.indexer_project(p, a, c_q, pos, cfg)
            idxp = _write(idxp, f, rows, ki)
            if decode:
                scores = index_scores(
                    qi[:, None], w[:, None], idxp, table, limits, f, tq=1,
                    name="mla_indexer_decode", use_kernel=use_kernel)[:, 0]
                selected[l] = M.top_positions(scores, valid, k)
            else:
                scores = index_scores(
                    qi[None], w[None], idxp, table, limits, f,
                    tq=min(_INDEX_TILE, x.shape[0]),
                    name="mla_indexer_prefill", use_kernel=use_kernel)[0]
                selected[l] = jnp.where(M.exact_topk_mask(scores, valid, k),
                                        0.0, -1e30).astype(jnp.bfloat16)
        q_abs = M.absorbed_query(p, q_nope, q_rope, cfg)
        if decode:
            positions, ok = selected[cfg.share_source(l)]
            chosen.append(jnp.where(ok, positions, -1))
            o_lat = sparse_mla(q_abs, lat, l, table, positions,
                               jnp.sum(ok, 1), _scale(cfg), cfg.kv_lora_rank,
                               name="sparse_mla_decode",
                               use_kernel=use_kernel)
        else:
            o_lat = masked_mla_chunk(
                q_abs, lat, l, table, pos[0], selected[cfg.share_source(l)],
                _scale(cfg), cfg.kv_lora_rank, name="sparse_mla_prefill",
                use_kernel=use_kernel)
        x = x + M.mla_output(p, o_lat, cfg, x.dtype)
        out, counts, route = M.ffn(p, M.rms_norm(x, p["ffn_norm"], eps), l,
                                   cfg, real, use_kernel)
        x = x + out
        if counts is not None:
            loads.append(counts)
            routes.append(route)
    probe = None
    if decode:
        probe = (jnp.stack(chosen, 1), jnp.stack([e for _, e in routes], 1),
                 jnp.stack([g for g, _ in routes], 1))
    return lat, idxp, x, probe, jnp.stack(loads)


def glm5_decode_fn(params, lat, idxp, state, *, cfg, use_kernel=True,
                   return_logits=False):
    """One token for every (possibly idle) bucket row.

    ``state`` ``int32[B, 2 + pages]``: a row's last token, its length
    including that token (0 = idle: its rows land in the sink page), its
    page-table row. Returns ``(lat, idxp, out [B + moe layers * held]
    int32, probe)``: ``out`` the next tokens, then the held experts'
    assignments; ``probe`` the positions each layer's attention read and
    each expert layer's choice and gates (:func:`_layers`); the logits
    ``[B, V]`` after them where ``return_logits``."""
    B = state.shape[0]
    ps = lat.shape[2]
    tokens, lens, table = state[:, _TOKEN], state[:, _LEN], state[:, _TABLE:]
    pos = jnp.maximum(lens - 1, 0)
    rows = table[jnp.arange(B), pos // ps] * ps + pos % ps
    width = table.shape[1] * ps
    valid = jnp.arange(width, dtype=jnp.int32)[None] < lens[:, None]
    x = params["embed"][tokens]
    lat, idxp, x, chosen, loads = _layers(
        params, lat, idxp, x, pos, rows, table, lens, valid, lens > 0, True,
        cfg, use_kernel)
    logits = M.final_logits(params, x, cfg)
    out = jnp.concatenate([jnp.argmax(logits, -1).astype(jnp.int32),
                           loads.reshape(-1)])
    return (lat, idxp, out, chosen) + ((logits,) if return_logits else ())


def glm5_chunk_fn(params, lat, idxp, ids, q_offset, chunk_len, page_table,
                  dest_rows, *, cfg, use_kernel=True, return_logits=False):
    """One chunk of one prompt (``ids`` ``[1, C]`` padded, the first
    ``chunk_len`` real, at positions ``q_offset + i``). Returns ``(lat,
    idxp, (out [1 + moe layers * held] int32,))``: the token after the
    chunk's last real position, then the held experts' assignments (and
    that position's logits ``[V]`` after it where ``return_logits``). Its
    queries' selections stay inside the program."""
    C = ids.shape[1]
    q_offset = jnp.asarray(q_offset, jnp.int32)
    chunk_len = jnp.asarray(chunk_len, jnp.int32)
    table = page_table.astype(jnp.int32)
    at = jnp.arange(C, dtype=jnp.int32)
    pos = q_offset + at
    width = table.shape[1] * lat.shape[2]
    valid = jnp.arange(width, dtype=jnp.int32)[None] <= pos[:, None]
    x = params["embed"][ids[0]]
    lat, idxp, x, _, loads = _layers(
        params, lat, idxp, x, pos, dest_rows.astype(jnp.int32), table,
        jnp.reshape(q_offset + chunk_len, (1,)), valid, at < chunk_len,
        False, cfg, use_kernel)
    last = jax.lax.dynamic_slice_in_dim(x, chunk_len - 1, 1, 0)
    logits = M.final_logits(params, last, cfg)
    out = jnp.concatenate([jnp.argmax(logits, -1).astype(jnp.int32),
                           loads.reshape(-1)])
    return lat, idxp, (out,) + ((logits[0],) if return_logits else ())


class Glm5ServingEngine(PagedEngine):
    """See the module docstring. ``params`` is
    :func:`paddle_tpu.models.glm5.init_glm5_weights`' layout (placed on
    the pool's device here); greedy only. ``keep_logits`` (tests) makes
    both programs return their logits too and keeps the last call's in
    ``last_logits``."""

    def __init__(self, params, config: M.Glm5Config, *, page_size=512,
                 num_pages=None, max_seq_len=None, decode_buckets=(1, 2, 4),
                 prefill_chunk=1024, prefix_cache=False, use_kernel=True,
                 aot=True, keep_logits=False, selection_every=0):
        if prefix_cache:
            raise ValueError(
                "prefix_cache: a hit would map latent and index pages whose "
                "selections nothing has tested; serve this model with "
                "prefix_cache=False")
        if prefill_chunk is None:
            raise ValueError("this engine prefills in chunks only")
        cfg = self.cfg = config
        self.use_kernel = bool(use_kernel)
        self.keep_logits = bool(keep_logits)
        self.selection_every = int(selection_every)
        super().__init__(
            params, num_layers=cfg.num_hidden_layers, num_kv_heads=1,
            head_dim=cfg.row_width, dtype=params["embed"].dtype,
            rows=((cfg.num_hidden_layers,
                   (1, row_words(cfg.row_width, params["embed"].dtype)),
                   jnp.uint32),
                  (len(cfg.full_layers), (cfg.index_head_dim,),
                   params["embed"].dtype)),
            max_positions=cfg.max_position_embeddings, page_size=page_size,
            num_pages=num_pages, max_seq_len=max_seq_len,
            decode_buckets=decode_buckets, prefill_chunk=prefill_chunk,
            prefix_cache=False)
        self._last_token: dict = {}
        self._pending_loads: list = []  # (output head, real rows, program)
        self.selection_log: dict = {}
        self.last_logits = None
        self.counters = {"prefill_chunks": 0, "decode_ticks": 0}
        self._build_programs()
        if aot:
            self.compile_buckets()

    # ------------------------------------------------------------- build
    def _build_programs(self):
        on_chip = jax.default_backend() != "cpu"
        kw = dict(cfg=self.cfg, use_kernel=self.use_kernel,
                  return_logits=self.keep_logits)
        self._decode_jit = jax.jit(
            functools.partial(glm5_decode_fn, **kw),
            donate_argnums=(1, 2) if on_chip else ())
        self._chunk_jit = jax.jit(
            functools.partial(glm5_chunk_fn, **kw),
            donate_argnums=(1, 2) if on_chip else ())
        self._decode_exe, self._chunk_exe = {}, None

    def _decode_avals(self, b):
        return (self._aval((b, _TABLE + self.pool.max_pages_per_seq),
                           jnp.int32),)

    # ------------------------------------------------------------ report
    @property
    def cache_bytes_per_token(self) -> int:
        """Bytes a live token holds as laid out: its latent rows (filled
        to ``row_width``) and index keys."""
        p = self.pool
        return (p.k_pages.nbytes + p.aux_pages.nbytes) \
            // (p.num_pages * p.page_size)

    @property
    def cache_bytes_needed(self) -> int:
        """Bytes a token's latent rows and index keys hold unfilled:
        ``latent_dim`` numbers a layer, ``index_head_dim`` a layer with an
        indexer."""
        cfg, size = self.cfg, self.pool.aux_pages.dtype.itemsize
        return size * (cfg.num_hidden_layers * cfg.latent_dim
                       + len(cfg.full_layers) * cfg.index_head_dim)

    def status(self) -> dict:
        st = super().status()
        st.update(cache_bytes_per_token=self.cache_bytes_per_token,
                  cache_bytes_needed=self.cache_bytes_needed,
                  index_topk=self.cfg.index_topk,
                  experts_held=self.cfg.experts_held,
                  counters=dict(self.counters))
        return st

    # --------------------------------------------------------- counters
    def _read_loads(self):
        """Read the held experts' counts of every program dispatched so
        far (a readback has just waited for all of them)."""
        for head, n, program in self._pending_loads:
            # after the tokens: [moe layers * held] counts
            c = np.asarray(head)[-len(self.cfg.moe_layers)
                                 * self.cfg.experts_held:].reshape(
                len(self.cfg.moe_layers), -1)
            with RecordEvent("experts.held", program=program, tokens=n,
                             assigned=[int(v) for v in c.sum(1)],
                             active=[int(v) for v in (c > 0).sum(1)]):
                pass
        self._pending_loads = []

    # ----------------------------------------------------------- prefill
    def prefill_begin(self, seq_id, prompt_ids) -> int:
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        n = int(prompt.shape[0])
        if n < 1 or n + 1 > self.max_seq_len:
            raise EngineShapeError(
                f"prompt of {n} tokens leaves no room for a token within "
                f"max_seq_len {self.max_seq_len}")
        return self._begin_prefill(seq_id, prompt, n)

    def prefill_step(self, seq_id):
        st = self._chunk_state[seq_id]
        self._chunk_at = st["pos"]
        self._chunk_tokens = min(self.prefill_chunk, st["n"] - st["pos"])
        return super().prefill_step(seq_id)

    def _chunk_attrs(self, final):
        """The chunk's work by the algorithm: ``index_keys`` (keys its
        queries score, ``s <= t``, a layer with an indexer) and
        ``selected`` (rows their attention reads, a layer)."""
        t = self._chunk_at + np.arange(1, self._chunk_tokens + 1)
        return {"index_keys": int(t.sum()),
                "selected": int(np.minimum(t, self.cfg.index_topk).sum())}

    def _chunk_issued(self, out):
        self.counters["prefill_chunks"] += 1
        self._pending_loads.append((out[0], self._chunk_tokens, "chunk"))

    def _chunk_read(self, seq_id, out):
        """A prompt's last chunk yields the first token."""
        head = np.asarray(out[0])
        if self.keep_logits:
            self.last_logits = np.asarray(out[1])
        self._read_loads()
        tok = int(head[0])
        self._last_token[seq_id] = tok
        return tok

    # ------------------------------------------------------------ decode
    def decode(self, seq_ids, bucket=None):
        """One decode step for ``seq_ids`` (each already holding its new
        position via ``pool.extend``), padded to ``bucket`` idle rows.
        Returns the next token per live sequence (list of ints)."""
        n = len(seq_ids)
        bucket = self.decode_bucket(n) if bucket is None else bucket
        if n > bucket:
            raise EngineShapeError(f"{n} sequences > bucket {bucket}")
        rows = list(seq_ids) + [None] * (bucket - n)
        lens = self.pool.lens_array(rows)
        k = self.cfg.index_topk
        with RecordEvent(
                "engine.decode", n=n, bucket=bucket,
                in_flight=self._in_flight, live_ctx=int(lens.sum()),
                selected=int(np.minimum(lens, k).sum()),
                cache_bytes=self.pool.pages_in_use * self.pool.page_size
                * self.cache_bytes_per_token):
            with RecordEvent("engine.host_prep", h2d=1):
                state = np.empty(
                    (bucket, _TABLE + self.pool.max_pages_per_seq), np.int32)
                state[:, _TOKEN] = [self._last_token.get(s, 0) for s in rows]
                state[:, _LEN] = lens
                state[:, _TABLE:] = self.pool.table_array(rows)
                fn = self._decode_exe.get(bucket, self._decode_jit)
                arg = jnp.asarray(state)
            with RecordEvent("engine.dispatch"):
                out = fn(self.params, self.pool.k_pages,
                         self.pool.aux_pages, arg)
                self.pool.bind(out[0], out[1])
            self._in_flight += 1
            with RecordEvent("engine.readback", in_flight=self._in_flight):
                head = np.asarray(out[2])
                nxt = [int(t) for t in head[:n]]
                if self.keep_logits:
                    self.last_logits = np.asarray(out[4])[:n]
            self._in_flight = 0
            self._pending_loads.append((head, n, "decode"))
            self._read_loads()
            self.counters["decode_ticks"] += 1
            for sid, t in zip(seq_ids, nxt):
                self._last_token[sid] = t
            if self.selection_every:
                self._log_selections(seq_ids, lens, out[3])
        return nxt

    def _log_selections(self, seq_ids, lens, probe):
        """Keep what the rows whose position is a multiple of
        ``selection_every`` selected: the tick's whole arrays read back
        once (an index into them on the device would be a program of its
        own, which would compile at the first such tick)."""
        at = lens[:len(seq_ids)].astype(np.int64) - 1
        rows = np.nonzero(at % self.selection_every == 0)[0]
        if not rows.size:
            return
        chosen, experts, gates = (np.asarray(a) for a in probe)
        for i in rows:
            self.selection_log.setdefault(seq_ids[i], []).append(
                (int(at[i]), chosen[i], experts[i], gates[i]))

    def _forget(self, seq_id):
        self._last_token.pop(seq_id, None)
