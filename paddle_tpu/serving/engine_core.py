"""The paged-engine core: what every engine over a page pool does and no
model decides, and the contract between such an engine and its callers.

- :class:`EngineContract`: what
  :class:`~.scheduler.ContinuousBatchingScheduler` and the fleet's replica
  loop may rely on. They read its attributes and call its methods; they
  never probe an engine with ``getattr``/``hasattr``. The scheduler's
  device-free stand-in (``scheduler._ShapeProbeEngine``) is the contract
  and nothing else.
- :class:`PagedEngine`: the core under the model adapters
  (:class:`~.engine.ServingEngine` for GPT,
  :class:`~.sdar_engine.SdarServingEngine` for SDAR-MoE): the
  :class:`~.kv_pool.PagePool` and :class:`~.prefix_cache.PrefixCache`, the
  weights placed on the pool's device, one AOT-compiled decode program a
  bucket and the one chunk program, ``status()``, the chunked prefill
  (spans, padded ids, page rows, dispatch, the last chunk's readback,
  publication to the prefix cache) and ``release()``. It never asks which
  model it serves.

**What an adapter states.** Its step functions stay globals of its own
module (tests and the benchmark's planted faults rebind them there). It
jits them in ``_build_programs()``, which also empties the executable
tables, so that ``_build_programs(); compile_buckets()`` re-makes every
program from the module's functions as they stand. It gives the avals of
a decode call at a bucket (``_decode_avals``) and what its chunk program
takes after the common five arguments (``_chunk_extra_avals`` /
``_chunk_extra_args``: GPT's sampling key; a model with state beside
the pages passes its donated state arrays there, gets them back in what
the program returns after the pools and rebinds them in
``_chunk_issued``), says what a prompt's last chunk reads back (``_chunk_read``: GPT the first token, SDAR the pending
expert counts), forgets its per-sequence state on release (``_forget``),
and owns ``decode()``: the host's part of a tick is model-shaped.
``_alloc_prompt`` here maps whole cached pages only, which is exact for
any model; GPT overrides it with its copy-on-write boundary page, which a
block model cannot use.

**The pool stays where it is.** Every decode and chunk program carries
the whole ``[L, P, ps, nkv, d]`` K and V pools through its layer loop (the
``lax.scan`` carry; the ``xs`` are the stacked weights and the layer
index). A layer writes its new rows at ``(layer, rows)``
(:func:`_write_rows`) and hands the paged kernel the whole pool with the
layer index: no layer's pages are cut out or written back into a second
pool. The pools are donated on TPU, so XLA aliases the carry to the
program's input and output, and ``status()["program_memory"]`` shows it:
in place means ``alias_bytes`` >= the pool's bytes and ``temp_bytes`` far
under.
"""
from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..profiler.utils import RecordEvent
from .kv_pool import PagePool
from .prefix_cache import PrefixCache

__all__ = ["EngineShapeError", "EngineContract", "PagedEngine",
           "smallest_bucket"]


class EngineShapeError(RuntimeError):
    """A shape outside the AOT-compiled bucket set was requested. The
    engine never recompiles at serving time — fix the bucket config."""


def _write_rows(pages, layer, rows, new):
    """Write ``new`` ``[n, nkv, d]`` into the carried pool ``[L, P, ps,
    nkv, d]`` at token rows ``rows`` of ``layer`` (a scatter of n rows
    on the ``[L, P*ps, nkv, d]`` view: in place on a loop-carried,
    donated buffer)."""
    L, np_, ps, nkv, d = pages.shape
    return pages.reshape(L, np_ * ps, nkv, d).at[layer, rows].set(
        new.astype(pages.dtype)).reshape(pages.shape)


def smallest_bucket(buckets, n: int, what: str) -> int:
    """The smallest of the sorted ``buckets`` that holds ``n`` of
    ``what`` (``"3 active sequences exceed the largest bucket, 2"``)."""
    for b in buckets:
        if n <= b:
            return b
    raise EngineShapeError(
        f"{n} {what} exceed the largest bucket, {buckets[-1]}")


class EngineContract:
    """What the scheduler and the fleet rely on, whatever the engine.
    Attributes (read, never probed):

    - ``decode_buckets``: the sorted batch sizes a decode step is
      compiled for; ``pool``: the :class:`~.kv_pool.PagePool`.
    - ``block_len``: positions a decode step advances a sequence by. 1:
      ``decode`` returns one token a sequence. More: a ``decode`` is a
      pass over each sequence's block and returns ``(tokens, passes,
      confidences)`` a sequence, empty until the block commits; prefill
      yields no token; and the scheduler also calls
      ``starts_block(seq_id)`` (the pool must grow by a block before the
      next pass), ``masked_positions(seq_ids)`` and
      ``note_emitted(emitted, dropped)``.
    - ``prefill_chunk``: None for a one-shot ``prefill(seq_id, prompt)
      -> token``; else the chunk of ``prefill_begin(seq_id, prompt) ->
      cached_len`` and ``prefill_step(seq_id) -> (tokens processed, done,
      first token or None)``.
    - ``prefix_cache``: the :class:`~.prefix_cache.PrefixCache`, or None.
    - ``can_migrate``: whether a running sequence can be handed to
      another engine (``export_kv`` / ``begin_kv_import`` /
      ``commit_kv_import`` / ``abort_kv_import``).

    Every engine also answers ``decode(seq_ids, bucket)``,
    ``release(seq_id, token_ids=None)`` and the three methods below."""

    block_len = 1
    prefill_chunk = None
    prefix_cache = None
    can_migrate = False
    decode_buckets: tuple = ()
    pool: PagePool

    def decode_bucket(self, n_active: int) -> int:
        return smallest_bucket(self.decode_buckets, n_active,
                               "active sequences")

    def reclaim_cache_pages(self, n_pages: int) -> int:
        """Evict LRU prefix-cache entries until ``n_pages`` returned to
        the free list (0 without a cache) — the scheduler's admission
        pressure valve: cache-held pages are free capacity until a
        paying sequence needs them."""
        if self.prefix_cache is None:
            return 0
        return self.prefix_cache.reclaim(int(n_pages))

    def status(self) -> dict:
        """Engine-side JSON snapshot for the live ``/status`` endpoint."""
        return {"decode_buckets": list(self.decode_buckets),
                "prefill_chunk": self.prefill_chunk,
                "block_len": self.block_len,
                "pool": self.pool.stats()}


def _program_sizes(exe) -> dict:
    m = exe.memory_analysis()
    return {"temp_bytes": int(m.temp_size_in_bytes),
            "alias_bytes": int(m.alias_size_in_bytes)}


class PagedEngine(EngineContract):
    """See the module docstring. ``params`` is the adapter's stacked
    weight tree (placed on the pool's device here); the pool is sized by
    ``num_layers`` x ``num_kv_heads`` x ``head_dim`` of ``dtype``, and
    ``max_positions`` is what the model can address."""

    def __init__(self, params, *, num_layers, num_kv_heads, head_dim, dtype,
                 max_positions, page_size, num_pages, max_seq_len,
                 decode_buckets, prefill_chunk, prefix_cache,
                 flat_rows=False):
        max_seq_len = int(max_seq_len or max_positions)
        if max_seq_len > max_positions:
            raise ValueError(f"max_seq_len {max_seq_len} exceeds the "
                             f"model's {max_positions} positions")
        self.max_seq_len = max_seq_len
        self.decode_buckets = tuple(sorted({int(b) for b in decode_buckets}))
        if prefill_chunk is not None:
            prefill_chunk = int(prefill_chunk)
            if prefill_chunk < 1 or prefill_chunk % page_size:
                raise ValueError(
                    f"prefill_chunk {prefill_chunk} must be a positive "
                    f"multiple of page_size {page_size} (chunks scatter "
                    f"whole page rows)")
        self.prefill_chunk = prefill_chunk
        self.compute_dtype = dtype
        if num_pages is None:
            # worst case: every slot of the widest bucket at full length,
            # plus the sink page
            num_pages = self.decode_buckets[-1] * math.ceil(
                max_seq_len / page_size) + 1
        self.pool = PagePool(num_pages, page_size, num_layers=num_layers,
                             num_kv_heads=num_kv_heads, head_dim=head_dim,
                             dtype=dtype, max_seq_len=max_seq_len,
                             flat_rows=flat_rows)
        # the weights live with the pool, where the programs run: a
        # model built on another backend (host-side init) would
        # otherwise cross to the device again on every call
        self.params = jax.device_put(
            params, next(iter(self.pool.k_pages.devices())))
        self.prefix_cache = PrefixCache(self.pool) if prefix_cache else None
        self._chunk_state: dict = {}    # seq_id -> in-flight prefill
        # programs dispatched since the last readback: a readback waits
        # for all of them, so this tells a decode that waited for its
        # own program from one that also waited for a chunk
        self._in_flight = 0
        self._decode_exe: dict = {}
        self._chunk_exe = None
        self._program_memory: dict = {"decode": {}}
        self.compile_s = 0.0

    # ------------------------------------------------------------- build
    def _aval(self, shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    def _decode_avals(self, bucket) -> tuple:
        """Avals of a decode call at ``bucket``, after ``(params, k_pages,
        v_pages)``."""
        raise NotImplementedError

    def _chunk_extra_avals(self) -> tuple:
        """Avals of what the chunk program takes after ``(ids, q_offset,
        chunk_len, page_table, dest_rows)``."""
        return ()

    def _chunk_extra_args(self, seq_id, final) -> tuple:
        """What a chunk of ``seq_id`` (its last where ``final``) passes
        after the common five: arrays an adapter keeps beside the pool
        ride here (donated by its jit, rebound in :meth:`_chunk_issued`)."""
        return ()

    def _chunk_attrs(self, final) -> dict:
        """Further attributes of a chunk's ``engine.prefill_step`` span."""
        return {}

    def _compile_more(self, params_avals, kp):
        """The adapter's other serving-time programs."""

    def compile_buckets(self):
        """AOT-compile what is missing of the decode program of every
        bucket, the chunk program and the adapter's own, so that no
        request mix ever compiles at serving time. Records wall time in
        ``compile_s`` and the jit-compile telemetry counters."""
        from ..observability.instrument import record_compile
        t0 = time.perf_counter()
        p = self.pool
        kp = self._aval(p.k_pages.shape, p.k_pages.dtype)
        params_avals = jax.tree_util.tree_map(
            lambda a: self._aval(a.shape, a.dtype), self.params)
        i32 = jnp.int32
        for b in self.decode_buckets:
            if b not in self._decode_exe:
                self._decode_exe[b] = self._decode_jit.lower(
                    params_avals, kp, kp, *self._decode_avals(b)).compile()
        if self.prefill_chunk is not None and self._chunk_exe is None:
            # ONE chunk program: offset and length ride as traced
            # scalars, so every chunk of every prompt (and every
            # cached-prefix suffix) reuses the same executable
            C, S = self.prefill_chunk, jax.ShapeDtypeStruct
            self._chunk_exe = self._chunk_jit.lower(
                params_avals, kp, kp, S((1, C), i32), S((), i32),
                S((), i32), S((1, p.max_pages_per_seq), i32), S((C,), i32),
                *self._chunk_extra_avals()).compile()
        self._compile_more(params_avals, kp)
        self._program_memory = {"decode": {
            b: _program_sizes(e)
            for b, e in sorted(self._decode_exe.items())}}
        if self._chunk_exe is not None:
            self._program_memory["chunk"] = _program_sizes(self._chunk_exe)
        self.compile_s += time.perf_counter() - t0
        record_compile(time.perf_counter() - t0, what="serving_buckets")

    # ------------------------------------------------------------ report
    def weight_bytes(self) -> int:
        """HBM-resident bytes of the stacked weights — the number the
        memory-bound decode roofline streams per step."""
        return int(sum(
            int(getattr(leaf, "nbytes", 0) or 0)
            for leaf in jax.tree_util.tree_leaves(self.params)))

    def decode_signatures(self) -> set:
        """The closed set of decode step shapes: {(batch_bucket,
        pages_per_seq)} — what the recompile lint checks the scheduler
        against."""
        return {(b, self.pool.max_pages_per_seq)
                for b in self.decode_buckets}

    def prefill_signatures(self) -> set:
        """The closed set of prefill-side program shapes: the one chunk
        program's ``("chunk", C, pages_per_seq)``."""
        return {("chunk", self.prefill_chunk, self.pool.max_pages_per_seq)}

    def program_memory(self) -> dict:
        """What the compiler says of every AOT-compiled program that
        carries the pool: ``{"decode": {bucket: {...}}, "chunk": {...}}``
        with ``temp_bytes`` and ``alias_bytes`` from
        ``compiled.memory_analysis()``, read once when the programs
        compile. The pool is updated in place where ``alias_bytes`` >=
        ``pool_bytes`` (both donated pools are the program's outputs)
        and ``temp_bytes`` is far under it; no program without AOT
        (``aot=False``)."""
        return dict(self._program_memory,
                    pool_bytes=int(self.pool.k_pages.nbytes
                                   + self.pool.v_pages.nbytes))

    def status(self) -> dict:
        """The contract's snapshot plus weight and pool sizing and the
        compile accounting (with each pool-carrying program's
        temporaries and aliased bytes); the adapter adds its own."""
        st = super().status()
        st.update(
            compute_dtype=str(np.dtype(self.compute_dtype)),
            weights_mb=round(self.weight_bytes() / 2 ** 20, 2),
            max_seq_len=self.max_seq_len,
            compile_s=round(self.compile_s, 3),
            aot_programs=len(self._decode_exe)
            + (self._chunk_exe is not None),
            program_memory=self.program_memory())
        if self.prefix_cache is not None:
            st["prefix_cache"] = self.prefix_cache.stats()
        return st

    # ----------------------------------------------------------- prefill
    def _begin_prefill(self, seq_id, tokens, prompt_len) -> int:
        """Pages for ``tokens`` (what of a prompt of ``prompt_len`` the
        chunk program prefills), the cached prefix mapped in, and the
        state :meth:`prefill_step` advances. Returns the cached prefix
        length."""
        with RecordEvent("engine.prefill_begin", rid=seq_id,
                         prompt_len=prompt_len) as ev:
            cached_len = self._alloc_prompt(seq_id, tokens)
            ev.set(cached_len=cached_len)
        self._chunk_state[seq_id] = {"prompt": tokens, "pos": cached_len,
                                     "n": int(tokens.shape[0])}
        return cached_len

    def _alloc_prompt(self, seq_id, tokens) -> int:
        """Pages for ``tokens``, cached whole pages mapped in; returns
        the cached prefix length."""
        n = int(tokens.shape[0])
        if self.prefix_cache is None:
            self.pool.note_prefix_lookup(0)
            with RecordEvent("pool.alloc"):
                self.pool.alloc(seq_id, n)
            return 0
        cache = self.prefix_cache
        with RecordEvent("prefix.match"):
            nodes, _boundary, _ = cache.match(tokens)
            pages = cache.map_into(seq_id, nodes, None)
        cached_len = len(nodes) * self.pool.page_size
        with RecordEvent("pool.alloc", cow=False):
            try:
                self.pool.alloc_prefixed(seq_id, n, pages, cached_len)
            except Exception:
                cache.release(seq_id)
                raise
        return cached_len

    def _chunk_issued(self, out):
        """After every chunk's dispatch: ``out`` is what the chunk
        program returned after the pools, not read back."""

    def _chunk_read(self, seq_id, out):
        """Read back a prompt's last chunk (inside ``engine.readback``):
        the first token, or None where prefill yields none."""
        raise NotImplementedError

    def prefill_step(self, seq_id):
        """Run ONE chunk of an in-flight prefill. Returns ``(tokens
        processed, done, first_token_or_None)`` — the scheduler spends
        its per-tick prefill token budget on these, so a long prompt
        interleaves with decode ticks instead of stalling them. Only a
        prompt's last chunk reads back, so that a finished prefill is a
        finished program."""
        st = self._chunk_state[seq_id]
        start, n = st["pos"], st["n"]
        C = self.prefill_chunk
        clen = min(C, n - start)
        final = start + clen >= n
        with RecordEvent("engine.prefill_step", rid=seq_id, start=start,
                         clen=clen, final=final, in_flight=self._in_flight,
                         **self._chunk_attrs(final)):
            with RecordEvent("engine.host_prep"):
                ids = np.zeros((1, C), np.int32)
                ids[0, :clen] = st["prompt"][start:start + clen]
                rows = self.pool.chunk_rows(seq_id, start, C)
                table = self.pool.table_array([seq_id])
                fn = self._chunk_exe if self._chunk_exe is not None \
                    else self._chunk_jit
                args = (jnp.asarray(ids), jnp.asarray(np.int32(start)),
                        jnp.asarray(np.int32(clen)), jnp.asarray(table),
                        jnp.asarray(rows)) \
                    + self._chunk_extra_args(seq_id, final)
            with RecordEvent("engine.dispatch"):
                kp, vp, out = fn(self.params, self.pool.k_pages,
                                 self.pool.v_pages, *args)
                self.pool.bind(kp, vp)
            self._in_flight += 1
            self._chunk_issued(out)
            st["pos"] = start + clen
            if not final:
                return clen, False, None
            with RecordEvent("engine.readback", in_flight=self._in_flight):
                tok = self._chunk_read(seq_id, out)
            self._in_flight = 0
            del self._chunk_state[seq_id]
            if self.prefix_cache is not None:
                # content now exists: publish the prompt's full pages so
                # queued same-prefix requests hit them
                self.prefix_cache.insert(st["prompt"],
                                         self.pool.table(seq_id))
        return clen, True, tok

    # ----------------------------------------------------------- release
    def _forget(self, seq_id):
        """Drop the adapter's per-sequence host state."""

    def release(self, seq_id, token_ids=None):
        """Free a sequence, finished or not. With a prefix cache,
        ``token_ids`` (the tokens whose K/V actually entered the pool)
        publishes the sequence's full pages into the trie first, so
        multi-turn follow-ups and repeated completions become cache
        hits."""
        self._forget(seq_id)
        self._chunk_state.pop(seq_id, None)
        if self.prefix_cache is not None:
            if token_ids is not None and len(token_ids):
                ids = np.asarray(token_ids, np.int32).reshape(-1)
                valid = min(int(ids.shape[0]), self.pool.seq_len(seq_id))
                self.prefix_cache.insert(ids[:valid],
                                         self.pool.table(seq_id))
            self.prefix_cache.release(seq_id)
        self.pool.free(seq_id)
