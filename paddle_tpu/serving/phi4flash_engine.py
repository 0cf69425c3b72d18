"""Hybrid serving engine: Phi-4-mini-flash from a page pool and a state
pool.

``Phi4FlashServingEngine`` is the adapter over the paged-engine core
(:mod:`.engine_core`: the page pool, the AOT bucket set, ``status()``, the
chunked prefill's skeleton and ``release()`` are
:class:`~.engine_core.PagedEngine`'s) for a model most of whose layers
keep a state of constant size (:mod:`paddle_tpu.models.phi4flash`): of 32
layers 9 are state-space, 8 attend a window, 14 own no cache at all, and
one full-attention layer's K/V is the only cache that grows. So the
engine holds **two kinds of cache in one manager**: the
:class:`~.kv_pool.PagePool` built with ``num_layers=1`` and
``flat_rows`` (a token's ``kv_pairs`` pairs of heads side by side: 5,120
B a token at the published widths where 32 attention layers would hold
163,840), and a
:class:`~.state_pool.StatePool` with a slot a sequence (state, convolution
tail, window rows), taken in ``prefill_begin``, zeroed inside the first
chunk's program, freed in ``release``. ``block_len`` is 1: the scheduler
drives it as it drives the GPT engine.

Two programs, the page pool and the four state arrays carried, donated
and updated in place (``status()["program_memory"]``):

- :func:`phi4flash_decode_fn`, one per decode bucket. A tick's one packed
  int32 array gives every bucket row its last token, length, slot and
  page-table row. The self-decoder is a ``lax.scan`` over (Mamba, window)
  pairs: gather the rows' states, step them, scatter them back; write the
  new window row at ``pos % window`` and attend the ring. Then the memory
  layer, then the full layer, which writes its row into the page pool
  and reads the pages; then a ``lax.scan`` over (memory unit, cross
  attention) pairs that read **the same pages**, the head, and argmax.
- :func:`phi4flash_chunk_fn`, one program for every chunk of every
  prompt (batch 1, 256 positions): the self-decoder over the chunk with
  the slot's state carried in and out (positions past ``chunk_len`` leave
  it untouched), and **on a prompt's last chunk only** (a ``lax.cond`` on
  a flag: one program, one compile; two programs would compile the
  self-decoder twice) the cross-decoder and the head at the prompt's last
  position alone, which yields the first token.

A tick's attention, over the shared pages and over the window rings
alike, is
:func:`paddle_tpu.kernels.paged_attention.paged_attention_decode_rows`:
both are **pools of rows** ``[L, pages, page, kv_pairs * 2 * head]``, a
token's KV pairs side by side on the lanes, so that a page is one dense
tile and a pair's keys a lane-aligned slice of it. (First built on the
``[.., nkv, d]`` kernel: ten heads of 128 were padded to sixteen in HBM,
and two pool heads of 640 made the kernel gather a head's row out of
every token's tile: 64% and 21% of a tick's device time on the chip,
whatever the page size.) A query pair becomes two zero-padded rows as
wide as a pair (:func:`paddle_tpu.models.phi4flash.paired_queries`), a
pair's rows filled to 16 for the MXU. In the trace the page pool's eight
readers a tick are ``shared_kv_attention_decode`` (a chunk's full layer
``shared_kv_attention_chunk``, the seven cross layers of a prompt's last
chunk ``shared_kv_attention_last``) and the eight window layers
``window_attention_decode``: a slot's ring is ``window / page``
fixed pages of every window layer, so the kernel reads them through a
table that follows from the slot, up to ``min(len, window)`` rows, and
nothing gathers a slot's rows into a batch. The chunk's scan is
:func:`paddle_tpu.kernels.selective_scan.selective_scan_chunk`, and its
full layer is the decode kernel with a position a row
(:func:`shared_attention_chunk`). Plain XLA: the one-position scan step,
and in the chunk program the window layers' attention (one sequence's
512 + 256 rows, the two score maps at their own width).
``use_kernel=False`` takes the reference path of both kernels.

``prefix_cache=True`` is refused: a hit would have to restore the state
as of the page boundary, which needs snapshots that nothing takes yet.
``can_migrate`` stays False for the same reason. Preemption needs
nothing: the scheduler re-prefills what it evicts, and a first chunk
resets the slot.

Spans: the core's ``engine.prefill_begin`` (with ``state.alloc`` under
it) and ``engine.prefill_step`` (``cross`` = 1 where the chunk ran the
cross-decoder), ``state.free`` in ``release``, ``sched.batch`` (the
live lengths and the counts below, worked out before ``engine.decode``
opens: the decode tick's time outside the ``engine.*`` spans, as it was
before it had a span), and ``engine.decode`` with ``engine.host_prep`` / ``dispatch`` /
``readback`` / ``unpack`` inside and the attributes ``live_ctx`` (sum of
the live lengths), ``window_rows`` (sum of ``min(len, window)``),
``state_slots`` (slots in use) and ``cache_bytes`` (the bytes of the
pages and of the slots in use).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.paged_attention import paged_attention_decode_rows
from ..models import phi4flash as M
from ..profiler.utils import RecordEvent
from .engine_core import EngineShapeError, PagedEngine, wait_for_device
from .state_pool import StatePool

__all__ = ["Phi4FlashServingEngine", "phi4flash_decode_fn",
           "phi4flash_chunk_fn", "paged_diff_attention",
           "shared_attention_chunk"]

# columns of a decode tick's packed state ``int32[bucket, 3 + pages]``
_TOKEN, _LEN, _SLOT, _TABLE = 0, 1, 2, 3
# the chunk program's flags ``int32[3]``
_F_SLOT, _F_RESET, _F_FINAL = 0, 1, 2


# the decode kernel's names in the trace: the page pool's readers of a
# tick (the full layer and the cross layers), the full layer of a chunk
# (a position a row), a prompt's last chunk's cross layers (one
# sequence), and the window layers' rings
SHARED_KV, SHARED_KV_CHUNK, SHARED_KV_LAST, WINDOW = (
    "shared_kv_attention_decode", "shared_kv_attention_chunk",
    "shared_kv_attention_last", "window_attention_decode")


def paged_diff_attention(q, k_rows, v_rows, page_table, seq_lens, cfg,
                         layer=0, use_kernel=True, name=SHARED_KV):
    """``(A1, A2)`` of one position a sequence over paged rows: ``q`` ``[B,
    q_pairs, 2 * head]``; a pool of rows ``[L, P, ps, kv_pairs * 2 *
    head]`` read at ``layer`` (the page pool's one layer, or a window
    layer's rings in the state pool) through ``page_table`` up to
    ``seq_lens`` rows. In float32, the kernel's accumulator as it stands:
    ``A1 - lam A2`` is a difference of nearly equal rows."""
    out = paged_attention_decode_rows(
        M.paired_queries(q, cfg), k_rows, v_rows,
        page_table, seq_lens, scale=cfg.head_dim ** -0.5, layer=layer,
        name=name, use_kernel=use_kernel)
    return M.unpair_outputs(out, cfg)


def shared_attention_chunk(q, k_rows, v_rows, page_table, q_offset,
                           chunk_len, cfg, use_kernel=True):
    """``(A1, A2)`` of a chunk of one sequence over its pages (which hold
    the chunk's rows already): ``q`` ``[C, q_pairs, 2 * head]`` at
    positions ``q_offset + i``. The decode kernel again, **a position a
    row of its batch**: all rows share the sequence's table, row ``i``
    reads ``q_offset + i + 1`` keys (the causal rule is the kernel's
    length mask; a padded row reads none), so a chunk costs what its
    context holds and not what the table could (dense over the table's
    6,144 positions this layer was 22 of a chunk's 35 ms on the chip)."""
    C = q.shape[0]
    at = jnp.arange(C, dtype=jnp.int32)
    lens = jnp.where(at < chunk_len, q_offset + at + 1, 0)
    table = jnp.broadcast_to(page_table, (C, page_table.shape[1]))
    return paged_diff_attention(q, k_rows, v_rows, table, lens, cfg, 0,
                                use_kernel, SHARED_KV_CHUNK)


def _write_paged(k_rows, v_rows, layer, rows, k, v):
    """``k``, ``v`` ``[n, kv_pairs, 2 * head]`` into token rows ``rows`` of
    ``layer`` of a pool of rows ``[L, P, ps, W]`` (a scatter of n rows on
    the ``[L, P * ps, W]`` view: in place on a carried, donated buffer)."""
    L, P, ps, W = k_rows.shape
    put = lambda pool, new: pool.reshape(L, P * ps, W).at[layer, rows].set(
        new.reshape(-1, W).astype(pool.dtype)).reshape(pool.shape)
    return put(k_rows, k), put(v_rows, v)


def _pair_index(n):
    return jnp.arange(n, dtype=jnp.int32)


def _cross_decoder(params, x, m, k_pages, v_pages, page_table, seq_lens,
                   cfg, use_kernel, name=SHARED_KV):
    """Layers after the full one, the final norm and the head over one
    position a sequence: ``x`` ``[B, H]``, ``m`` ``[B, Di]`` the memory at
    that position. Logits ``[B, V]``."""

    def pair(x, xs):
        (pg, pc), j = xs
        x = M.mlp(pg, M.memory_unit(pg, x, m, cfg), cfg)
        q, _, _ = M.attn_project(pc, x, cfg, own_kv=False)
        a1, a2 = paged_diff_attention(q, k_pages, v_pages, page_table,
                                      seq_lens, cfg, 0, use_kernel, name)
        x = x + M.diff_combine(pc, a1, a2, cfg.full_layer + 2 + 2 * j, cfg)
        return M.mlp(pc, x, cfg), None

    cp = params["cross_pairs"]
    x, _ = jax.lax.scan(pair, x, ((cp["gmu"], cp["cross"]),
                                  _pair_index(cfg.n_cross_pairs)))
    return M.final_logits(params, x, cfg)


def phi4flash_decode_fn(params, k_pages, v_pages, ssm, conv, win_k, win_v,
                        state, *, cfg, use_kernel=True,
                        return_logits=False):
    """One token for every (possibly idle) bucket row.

    ``state`` ``int32[B, 3 + pages_per_seq]``: a row's last token, its
    length including that token (0 = idle: its rows land in the sink page
    and the sink slot), its slot, its page-table row. Returns ``(k_pages,
    v_pages, ssm, conv, win_k, win_v, next tokens [B])`` (and the logits
    ``[B, V]`` after them where ``return_logits``)."""
    B = state.shape[0]
    ps, window = k_pages.shape[2], cfg.sliding_window
    tokens, seq_lens, slots = state[:, _TOKEN], state[:, _LEN], \
        state[:, _SLOT]
    page_table = state[:, _TABLE:]
    pos = jnp.maximum(seq_lens - 1, 0)
    rows = page_table[jnp.arange(B), pos // ps] * ps + pos % ps
    # a slot's ring is pages slot * ring_pages ... of every window layer
    ring_table = slots[:, None] * (window // ps) \
        + jnp.arange(window // ps, dtype=jnp.int32)[None]
    ring_rows = slots * window + pos % window
    ring_lens = jnp.minimum(seq_lens, window)
    x = params["embed"][tokens]

    def pair(carry, xs):
        x, ssm, conv, wk, wv = carry
        (pm, pa), i = xs
        x, s, t, _ = M.mamba_step(pm, x, ssm[i, slots], conv[i, slots], cfg)
        ssm, conv = ssm.at[i, slots].set(s), conv.at[i, slots].set(t)
        x = M.mlp(pm, x, cfg)
        q, k, v = M.attn_project(pa, x, cfg)
        wk, wv = _write_paged(wk, wv, i, ring_rows, k, v)
        a1, a2 = paged_diff_attention(q, wk, wv, ring_table, ring_lens,
                                      cfg, i, use_kernel, WINDOW)
        x = x + M.diff_combine(pa, a1, a2, 2 * i + 1, cfg)
        return (M.mlp(pa, x, cfg), ssm, conv, wk, wv), None

    sp, P = params["self_pairs"], cfg.n_self_pairs
    (x, ssm, conv, win_k, win_v), _ = jax.lax.scan(
        pair, (x, ssm, conv, win_k, win_v),
        ((sp["mamba"], sp["attn"]), _pair_index(P)))
    p16, p17 = params["l16"], params["l17"]
    x, s, t, m = M.mamba_step(p16, x, ssm[P, slots], conv[P, slots], cfg)
    ssm, conv = ssm.at[P, slots].set(s), conv.at[P, slots].set(t)
    x = M.mlp(p16, x, cfg)
    q, k, v = M.attn_project(p17, x, cfg)
    k_pages, v_pages = _write_paged(k_pages, v_pages, 0, rows, k, v)
    a1, a2 = paged_diff_attention(q, k_pages, v_pages, page_table,
                                  seq_lens, cfg, 0, use_kernel)
    x = M.mlp(p17, x + M.diff_combine(p17, a1, a2, cfg.full_layer, cfg),
              cfg)
    logits = _cross_decoder(params, x, m, k_pages, v_pages, page_table,
                            seq_lens, cfg, use_kernel)
    out = (k_pages, v_pages, ssm, conv, win_k, win_v,
           jnp.argmax(logits, -1).astype(jnp.int32))
    return out + (logits,) if return_logits else out


def phi4flash_chunk_fn(params, k_pages, v_pages, ids, q_offset, chunk_len,
                       page_table, dest_rows, ssm, conv, win_k, win_v,
                       flags, *, cfg, use_kernel=True, return_logits=False):
    """One chunk of one prompt (``ids`` ``[1, C]`` padded, the first
    ``chunk_len`` real, at positions ``q_offset + i``).

    ``flags`` ``int32[3]``: the sequence's slot, 1 where this is its
    first chunk (the slot's state and tail start from zero), 1 where it
    is the last (the cross-decoder and the head then run at position
    ``chunk_len - 1``). Returns ``(k_pages, v_pages, (ssm, conv, win_k,
    win_v, token [1]))``, the token 0 unless the chunk was the last (and
    the last position's logits ``[V]`` after the token where
    ``return_logits``)."""
    C = ids.shape[1]
    slot, fresh, final = flags[_F_SLOT], flags[_F_RESET] > 0, \
        flags[_F_FINAL] > 0
    q_offset = jnp.asarray(q_offset, jnp.int32)
    chunk_len = jnp.asarray(chunk_len, jnp.int32)
    page_table = page_table.astype(jnp.int32)
    # the slot's ring in a window layer: its pages, whole
    ring = (1, cfg.sliding_window // win_k.shape[2]) + win_k.shape[2:]
    x = params["embed"][ids[0]]

    def mamba(p, x, ssm, conv, i):
        s = jnp.where(fresh, 0, ssm[i, slot])
        t = jnp.where(fresh, 0, conv[i, slot])
        x, s, t, m = M.mamba_chunk(p, x, s, t, chunk_len, cfg, use_kernel)
        return M.mlp(p, x, cfg), ssm.at[i, slot].set(s), \
            conv.at[i, slot].set(t), m

    def pair(carry, xs):
        x, ssm, conv, wk, wv = carry
        (pm, pa), i = xs
        x, ssm, conv, _ = mamba(pm, x, ssm, conv, i)
        q, k, v = M.attn_project(pa, x, cfg)
        zero = jnp.int32(0)
        at = (i, slot * jnp.int32(ring[1]), zero, zero)
        mine = lambda w: jax.lax.dynamic_slice(w, at, ring).reshape(
            -1, *k.shape[1:])
        a1, a2, rk, rv = M.window_chunk_attention(
            q, k, v, mine(wk), mine(wv), q_offset, chunk_len, cfg)
        wk = jax.lax.dynamic_update_slice(wk, rk.reshape(ring), at)
        wv = jax.lax.dynamic_update_slice(wv, rv.reshape(ring), at)
        x = x + M.diff_combine(pa, a1, a2, 2 * i + 1, cfg)
        return (M.mlp(pa, x, cfg), ssm, conv, wk, wv), None

    sp, P = params["self_pairs"], cfg.n_self_pairs
    (x, ssm, conv, win_k, win_v), _ = jax.lax.scan(
        pair, (x, ssm, conv, win_k, win_v),
        ((sp["mamba"], sp["attn"]), _pair_index(P)))
    x, ssm, conv, m = mamba(params["l16"], x, ssm, conv, P)
    p17 = params["l17"]
    q, k, v = M.attn_project(p17, x, cfg)
    rows = dest_rows.astype(jnp.int32)
    k_pages, v_pages = _write_paged(k_pages, v_pages, 0, rows, k, v)
    a1, a2 = shared_attention_chunk(q, k_pages, v_pages, page_table,
                                    q_offset, chunk_len, cfg, use_kernel)
    x = M.mlp(p17, x + M.diff_combine(p17, a1, a2, cfg.full_layer, cfg),
              cfg)

    def cross(_):
        last = chunk_len - 1
        return _cross_decoder(
            params, jax.lax.dynamic_slice_in_dim(x, last, 1, 0),
            jax.lax.dynamic_slice_in_dim(m, last, 1, 0), k_pages, v_pages,
            page_table, jnp.reshape(q_offset + chunk_len, (1,)), cfg,
            use_kernel, SHARED_KV_LAST)

    logits = jax.lax.cond(
        final, cross,
        lambda _: jnp.zeros((1, params["embed"].shape[0]), jnp.float32),
        None)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    out = (ssm, conv, win_k, win_v, tok)
    return k_pages, v_pages, out + (logits[0],) if return_logits else out


class Phi4FlashServingEngine(PagedEngine):
    """See the module docstring. ``params`` is the stacked layout of
    :func:`paddle_tpu.models.phi4flash.phi4flash_weight_shapes` (placed
    on the pool's device here); greedy only. ``keep_logits`` (tests)
    makes both programs return their logits too and keeps the last
    call's in ``last_logits``."""

    def __init__(self, params, config: M.Phi4FlashConfig, *, page_size=64,
                 num_pages=None, max_seq_len=None,
                 decode_buckets=(1, 2, 4, 8), prefill_chunk=256,
                 prefix_cache=False, use_kernel=True, aot=True,
                 keep_logits=False):
        if prefix_cache:
            raise ValueError(
                "prefix_cache: a hit would have to restore the state-space "
                "and window state as of the page boundary, and nothing "
                "snapshots it yet; serve this model with prefix_cache=False")
        if prefill_chunk is None:
            raise ValueError("this engine prefills in chunks only")
        cfg = self.cfg = config
        self.use_kernel = bool(use_kernel)
        self.keep_logits = bool(keep_logits)
        dtype = params["embed"].dtype
        # a pool of rows: ten heads of 128 as a second-minor axis would be
        # padded to sixteen in HBM and gathered sublane by sublane in the
        # kernel; side by side on the lanes a page is one dense tile
        super().__init__(
            params, num_layers=1, num_kv_heads=cfg.kv_pairs,
            head_dim=cfg.pair_dim, dtype=dtype, flat_rows=True,
            max_positions=cfg.max_position_embeddings, page_size=page_size,
            num_pages=num_pages, max_seq_len=max_seq_len,
            decode_buckets=decode_buckets, prefill_chunk=prefill_chunk,
            prefix_cache=False)
        # a slot a sequence: the scheduler admits while running +
        # prefilling + migrating_in < max_concurrency = the widest
        # bucket, so alloc() below never finds the pool empty
        self.state = StatePool(
            self.decode_buckets[-1], n_ssm=cfg.n_mamba, d_state=cfg.d_state,
            d_inner=cfg.d_inner, d_conv=cfg.d_conv,
            n_window=cfg.n_self_pairs, window=cfg.sliding_window,
            page_size=page_size, row_width=cfg.kv_pairs * cfg.pair_dim,
            dtype=dtype)
        self._last_token: dict = {}
        self.last_logits = None
        self.counters = {"prefill_chunks": 0, "cross_chunks": 0,
                         "decode_ticks": 0}
        self._build_programs()
        if aot:
            self.compile_buckets()

    # ------------------------------------------------------------- build
    def _build_programs(self):
        """(Re)make the two jitted programs from the module's step
        functions as they stand."""
        on_chip = jax.default_backend() != "cpu"
        kw = dict(cfg=self.cfg, use_kernel=self.use_kernel,
                  return_logits=self.keep_logits)
        self._decode_jit = jax.jit(
            functools.partial(phi4flash_decode_fn, **kw),
            donate_argnums=(1, 2, 3, 4, 5, 6) if on_chip else ())
        self._chunk_jit = jax.jit(
            functools.partial(phi4flash_chunk_fn, **kw),
            donate_argnums=(1, 2, 8, 9, 10, 11) if on_chip else ())
        self._decode_exe, self._chunk_exe = {}, None

    def _state_avals(self):
        return tuple(self._aval(a.shape, a.dtype)
                     for a in self.state.arrays())

    def _decode_avals(self, b):
        return self._state_avals() + (self._aval(
            (b, _TABLE + self.pool.max_pages_per_seq), jnp.int32),)

    def _chunk_extra_avals(self):
        return self._state_avals() + (self._aval((3,), jnp.int32),)

    def _chunk_extra_args(self, seq_id, final):
        st = self._chunk_state[seq_id]
        flags = np.asarray([self.state.slot(seq_id), st["pos"] == 0, final],
                           np.int32)
        return self.state.arrays() + (jnp.asarray(flags),)

    def _chunk_attrs(self, final):
        return {"cross": int(final)}

    # ------------------------------------------------------------ report
    @property
    def cache_bytes_per_token(self) -> int:
        """Page-pool bytes a live token holds (K and V of one layer)."""
        p = self.pool
        return 2 * p.num_kv_heads * p.head_dim * p.k_pages.dtype.itemsize

    def program_memory(self) -> dict:
        """The core's figures and ``state_bytes``: in place means
        ``alias_bytes`` >= ``pool_bytes + state_bytes``."""
        return dict(super().program_memory(),
                    state_bytes=self.state.nbytes)

    def status(self) -> dict:
        st = super().status()
        st.update(state=self.state.stats(),
                  cache_bytes_per_token=self.cache_bytes_per_token,
                  state_bytes_per_slot=self.state.bytes_per_slot,
                  chunks=dict(self.counters))
        return st

    # ----------------------------------------------------------- prefill
    def prefill_begin(self, seq_id, prompt_ids) -> int:
        """Pages for the prompt and a state slot; returns 0 (no prefix is
        ever reused)."""
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        n = int(prompt.shape[0])
        if n < 1 or n + 1 > self.max_seq_len:
            raise EngineShapeError(
                f"prompt of {n} tokens leaves no room for a token within "
                f"max_seq_len {self.max_seq_len}")
        return self._begin_prefill(seq_id, prompt, n)

    def _alloc_prompt(self, seq_id, tokens) -> int:
        cached = super()._alloc_prompt(seq_id, tokens)
        try:
            with RecordEvent("state.alloc") as ev:
                ev.set(slot=self.state.alloc(seq_id))
        except Exception:
            self.pool.free(seq_id)
            raise
        return cached

    def _chunk_issued(self, out):
        self.state.bind(*out[:4])
        self.counters["prefill_chunks"] += 1

    def _chunk_read(self, seq_id, out):
        """A prompt's last chunk yields the first token."""
        self.counters["cross_chunks"] += 1
        if self.keep_logits:
            self.last_logits = np.asarray(out[5])
        tok = int(np.asarray(out[4])[0])
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
        with RecordEvent("sched.batch"):
            rows = list(seq_ids) + [None] * (bucket - n)
            lens = self.pool.lens_array(rows)
            window = self.cfg.sliding_window
            attrs = dict(
                n=n, bucket=bucket, in_flight=self._in_flight,
                live_ctx=int(lens.sum()),
                window_rows=int(np.minimum(lens, window).sum()),
                state_slots=self.state.slots_in_use,
                cache_bytes=self.pool.pages_in_use * self.pool.page_size
                * self.cache_bytes_per_token
                + self.state.slots_in_use * self.state.bytes_per_slot)
        with RecordEvent("engine.decode", **attrs):
            with RecordEvent("engine.host_prep"):
                state = np.empty(
                    (bucket, _TABLE + self.pool.max_pages_per_seq), np.int32)
                state[:, _TOKEN] = [self._last_token.get(s, 0) for s in rows]
                state[:, _LEN] = lens
                state[:, _SLOT] = self.state.slots_array(rows)
                state[:, _TABLE:] = self.pool.table_array(rows)
                fn = self._decode_exe.get(bucket, self._decode_jit)
                arg = jnp.asarray(state)
            with RecordEvent("engine.dispatch"):
                out = fn(self.params, self.pool.k_pages, self.pool.v_pages,
                         *self.state.arrays(), arg)
                self.pool.bind(out[0], out[1])
                self.state.bind(*out[2:6])
            self._in_flight += 1
            with RecordEvent("engine.readback", in_flight=self._in_flight):
                wait_for_device(out[6])
                with RecordEvent("engine.d2h"):
                    nxt = [int(t) for t in np.asarray(out[6])[:n]]
                    if self.keep_logits:
                        self.last_logits = np.asarray(out[7])[:n]
            self._in_flight = 0
            with RecordEvent("engine.unpack"):
                self.counters["decode_ticks"] += 1
                for sid, t in zip(seq_ids, nxt):
                    self._last_token[sid] = t
        return nxt

    def _forget(self, seq_id):
        self._last_token.pop(seq_id, None)
        with RecordEvent("state.free"):
            self.state.free(seq_id)
