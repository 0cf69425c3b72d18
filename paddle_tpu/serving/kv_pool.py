"""Block KV-cache pool: fixed-size HBM pages + per-sequence page tables.

The serving engine's memory manager. The pool owns two device arrays —
``k_pages``/``v_pages`` ``[num_layers, num_pages, page_size,
num_kv_heads, head_dim]`` — and the host-side bookkeeping that maps
sequences onto them: a free list and one page table (list of page ids)
per live sequence. Live HBM therefore tracks *actual tokens* (rounded up
to the page), not ``max_position_embeddings`` — the vLLM/"Ragged Paged
Attention" scheme. One table serves all ``num_layers`` of the pool, and
those are the layers whose cache grows with the sequence, which a model
may have fewer of than it has layers: the hybrid engine
(:mod:`.phi4flash_engine`) builds the pool with ``num_layers=1`` for a
model of 32, whose window and state-space layers keep a slot of constant
size in the :class:`~.state_pool.StatePool` beside it.

Page 0 is the reserved **sink** page: padding page-table entries and
padded prefill rows scatter into it, so every gather/scatter index the
compiled decode step computes is in-bounds by construction regardless of
how ragged the batch is. It is never allocated and never read unmasked.

The device arrays are updated *functionally*: the engine passes
``pool.k_pages`` into its jitted step (donated on TPU), gets the new
arrays back, and rebinds them via :meth:`bind`. The host bookkeeping
(``alloc``/``extend``/``free``) is plain Python — a few dict/list ops per
request per step, never on the device critical path.

Pages are **refcounted** so the prefix cache
(:mod:`paddle_tpu.serving.prefix_cache`) can map one physical page into
many sequences' page tables (and into the cache's own trie nodes): a
page returns to the free list only when its last reference drops.
Writers stay safe via the copy-on-write invariant — :meth:`extend`
refuses to grow a sequence into a page another holder still references
(the engine COWs the boundary page at admission, so a correctly driven
pool never trips this guard).
"""
from __future__ import annotations

import functools
import math

import jax.numpy as jnp
import numpy as np

from ..observability import lockwitness

__all__ = ["PagePool", "PagePoolError", "PagePoolOOM"]


def _locked(fn):
    """Run a bookkeeping method under the pool's internal RLock —
    the scheduler tick, admission, cancel, and the prefix cache all
    mutate one pool, possibly from different threads. Reentrant:
    alloc_prefixed -> incref and free -> decref nest."""
    @functools.wraps(fn)
    def wrapper(self, *args, **kwargs):
        with self._mu:
            return fn(self, *args, **kwargs)
    return wrapper


class PagePoolError(RuntimeError):
    """Bookkeeping misuse: unknown/duplicate sequence, bad token count."""


class PagePoolOOM(PagePoolError):
    """Not enough free pages to satisfy an allocation."""


class PagePool:
    SINK = 0  # reserved padding/garbage page, never allocated

    def __init__(self, num_pages, page_size, num_layers, num_kv_heads,
                 head_dim, dtype="float32", max_seq_len=None,
                 flat_rows=False):
        if num_pages < 2:
            raise ValueError("need at least 2 pages (one is the sink)")
        if page_size < 1:
            raise ValueError(f"page_size {page_size} must be >= 1")
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.num_layers = int(num_layers)
        self.num_kv_heads = int(num_kv_heads)
        self.head_dim = int(head_dim)
        self.max_seq_len = int(max_seq_len) if max_seq_len \
            else (num_pages - 1) * page_size
        # every decode shape carries the SAME pages-per-seq width: the
        # page-table operand is static, only the batch bucket varies
        self.max_pages_per_seq = max(
            1, math.ceil(self.max_seq_len / self.page_size))
        # flat_rows: a token's KV heads side by side, ``[L, P, ps, nkv *
        # d]`` (``paged_attention_decode_rows``): for a head count the
        # chip would pad as a second-minor axis (ten heads to sixteen)
        shape = (self.num_layers, self.num_pages, self.page_size) + (
            (self.num_kv_heads * self.head_dim,) if flat_rows
            else (self.num_kv_heads, self.head_dim))
        self.k_pages = jnp.zeros(shape, dtype=dtype)
        self.v_pages = jnp.zeros(shape, dtype=dtype)
        # internal lock: every bookkeeping mutator/reader below runs
        # under it (witness-named for the runtime lock witness)
        self._mu = lockwitness.named_rlock("serving.page_pool")
        # LIFO free list, deterministic: lowest page ids hand out first
        self._free = list(range(self.num_pages - 1, 0, -1))
        self._tables: dict = {}   # seq_id -> [page, ...]
        self._lens: dict = {}     # seq_id -> true token count
        self._refs: dict = {}     # page -> reference count (seqs + cache)
        # prefix-cache accounting (the cache reports into its pool so
        # one stats() snapshot carries pool AND reuse numbers)
        self._prefix_lookups = 0
        self._prefix_hits = 0
        self._tokens_reused = 0

    # ------------------------------------------------------------ sizing
    def pages_needed(self, n_tokens: int) -> int:
        return max(1, math.ceil(int(n_tokens) / self.page_size))

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def pages_in_use(self) -> int:
        return (self.num_pages - 1) - len(self._free)

    @property
    @_locked
    def live_tokens(self) -> int:
        return sum(self._lens.values())

    @property
    def live_sequences(self) -> int:
        return len(self._tables)

    @property
    @_locked
    def pages_shared(self) -> int:
        """Pages mapped by more than one holder (sequences and/or the
        prefix-cache trie) — >0 proves physical page reuse."""
        return sum(1 for c in self._refs.values() if c > 1)

    @_locked
    def note_prefix_lookup(self, tokens_reused: int):
        """Prefix-cache reuse accounting (called by the cache on every
        admission match attempt): a lookup reusing >0 tokens is a hit."""
        self._prefix_lookups += 1
        if tokens_reused > 0:
            self._prefix_hits += 1
            self._tokens_reused += int(tokens_reused)

    @_locked
    def stats(self) -> dict:
        """Fragmentation + sharing accounting: ``utilization`` = the
        PHYSICALLY occupied share of allocated page slots, so
        ``internal_fragmentation`` is the share of allocated HBM wasted
        on partially-filled trailing pages. Only a sequence's trailing
        page can be partial, and partial pages are always exclusive
        (the COW invariant), so waste sums per-sequence without double
        counting — and stays in [0, 1] even when shared pages make
        ``live_tokens`` (a logical, reuse-counting total) exceed the
        physical slot count. ``pages_shared`` / ``tokens_reused`` /
        ``prefix_hit_rate`` surface prefix-cache page reuse (all zero
        without a cache)."""
        cap = self.pages_in_use * self.page_size
        waste = sum((self.page_size - n % self.page_size)
                    % self.page_size for n in self._lens.values())
        util = ((cap - waste) / cap) if cap else 1.0
        itemsize = jnp.zeros((), self.k_pages.dtype).dtype.itemsize
        return {
            "num_pages": self.num_pages,
            "page_size": self.page_size,
            "pages_in_use": self.pages_in_use,
            "free_pages": self.free_pages,
            "live_sequences": self.live_sequences,
            "live_tokens": self.live_tokens,
            "capacity_tokens": (self.num_pages - 1) * self.page_size,
            "utilization": round(util, 4),
            "internal_fragmentation": round(1.0 - util, 4),
            "pool_bytes": 2 * int(np.prod(self.k_pages.shape)) * itemsize,
            "pages_shared": self.pages_shared,
            "tokens_reused": self._tokens_reused,
            # raw counts next to the rate so a FLEET can aggregate hit
            # rates exactly (sum hits / sum lookups), not average ratios
            "prefix_lookups": self._prefix_lookups,
            "prefix_hits": self._prefix_hits,
            "prefix_hit_rate": round(
                self._prefix_hits / self._prefix_lookups, 4)
            if self._prefix_lookups else 0.0,
        }

    # ------------------------------------------------------- bookkeeping
    def _require(self, seq_id):
        if seq_id not in self._tables:
            raise PagePoolError(
                f"unknown or already-freed sequence {seq_id!r} "
                f"({self.live_sequences} live)")

    @_locked
    def _take_page(self) -> int:
        """Pop one page off the free list at refcount 1 (caller owns it
        — used for COW boundary copies before a table exists)."""
        if not self._free:
            raise PagePoolOOM("no free pages for a copy-on-write page")
        p = self._free.pop()
        self._refs[p] = 1
        return p

    @_locked
    def incref(self, pages):
        """Add one reference per page (prefix-cache node adoption or
        mapping a cached page into a new sequence's table). Validates
        EVERY page before touching any refcount, so a bad batch leaves
        the pool untouched — same no-partial-mutation discipline as
        :meth:`extend`'s write barrier."""
        pages = list(pages)
        for p in pages:
            if p == self.SINK or not (0 < p < self.num_pages):
                raise PagePoolError(f"cannot reference page {p}")
            if p not in self._refs:
                raise PagePoolError(f"page {p} is not allocated")
        for p in pages:
            self._refs[p] += 1

    @_locked
    def decref(self, pages):
        """Drop one reference per page; pages reaching zero return to
        the free list (lowest ids reused first)."""
        freed = []
        for p in pages:
            c = self._refs.get(p, 0)
            if c < 1:
                raise PagePoolError(f"page {p} is not referenced")
            if c == 1:
                del self._refs[p]
                freed.append(p)
            else:
                self._refs[p] = c - 1
        self._free.extend(sorted(freed, reverse=True))
        return freed

    @_locked
    def page_ref(self, page: int) -> int:
        return self._refs.get(page, 0)

    def alloc(self, seq_id, n_tokens: int):
        """Register a new sequence holding ``n_tokens`` and hand it pages."""
        return self.alloc_prefixed(seq_id, n_tokens, (), 0)

    @_locked
    def alloc_prefixed(self, seq_id, n_tokens: int, prefix_pages,
                       prefix_len: int):
        """Register a new sequence whose first ``prefix_len`` tokens
        already live in ``prefix_pages`` (cached prefix pages the caller
        mapped — this takes one reference on each); only the pages
        covering tokens beyond the prefix draw from the free list.
        Returns the full page table."""
        if seq_id in self._tables:
            raise PagePoolError(f"sequence {seq_id!r} already allocated")
        n_tokens = int(n_tokens)
        prefix_len = int(prefix_len)
        prefix_pages = list(prefix_pages)
        if n_tokens < 1:
            raise PagePoolError(f"n_tokens {n_tokens} must be >= 1")
        if n_tokens > self.max_seq_len:
            raise PagePoolError(
                f"n_tokens {n_tokens} exceeds max_seq_len "
                f"{self.max_seq_len}")
        if prefix_len > n_tokens:
            raise PagePoolError(
                f"prefix_len {prefix_len} exceeds n_tokens {n_tokens}")
        if prefix_pages and not prefix_len:
            raise PagePoolError("prefix pages without a prefix length")
        if prefix_len and len(prefix_pages) != math.ceil(
                prefix_len / self.page_size):
            raise PagePoolError(
                f"prefix of {prefix_len} tokens needs "
                f"{math.ceil(prefix_len / self.page_size)} pages, "
                f"got {len(prefix_pages)}")
        need = self.pages_needed(n_tokens) - len(prefix_pages)
        if need > len(self._free):
            raise PagePoolOOM(
                f"need {need} pages for {n_tokens} tokens "
                f"({prefix_len} cached), {len(self._free)} free")
        self.incref(prefix_pages)
        fresh = []
        for _ in range(max(need, 0)):
            p = self._free.pop()
            self._refs[p] = 1
            fresh.append(p)
        self._tables[seq_id] = prefix_pages + fresh
        self._lens[seq_id] = n_tokens
        return list(self._tables[seq_id])

    @_locked
    def extend(self, seq_id, n_new: int = 1) -> int:
        """Grow a sequence by ``n_new`` tokens, allocating pages as the
        length crosses page boundaries. Returns the new length. The
        page the new tokens land in must be exclusively held (COW
        invariant): growing into a shared page would corrupt every
        other holder's cache."""
        self._require(seq_id)
        new_len = self._lens[seq_id] + int(n_new)
        if new_len > self.max_seq_len:
            raise PagePoolError(
                f"sequence {seq_id!r} would exceed max_seq_len "
                f"{self.max_seq_len}")
        table = self._tables[seq_id]
        need = self.pages_needed(new_len) - len(table)
        if need > len(self._free):
            raise PagePoolOOM(
                f"sequence {seq_id!r} needs {need} more page(s), "
                f"{len(self._free)} free")
        # the write barrier runs BEFORE any allocation so a refused
        # extend leaves the pool untouched: every EXISTING page
        # receiving one of the new tokens must be private to this
        # sequence (fresh pages are born private)
        first = self._lens[seq_id] // self.page_size
        last = (new_len - 1) // self.page_size
        for idx in range(first, min(last, len(table) - 1) + 1):
            p = table[idx]
            if self._refs.get(p, 0) != 1:
                raise PagePoolError(
                    f"sequence {seq_id!r} would write shared page {p} "
                    f"(refcount {self._refs.get(p, 0)}) — copy-on-write "
                    f"the boundary page before extending")
        for _ in range(need):
            p = self._free.pop()
            self._refs[p] = 1
            table.append(p)
        self._lens[seq_id] = new_len
        return new_len

    @_locked
    def free(self, seq_id):
        """Drop the sequence's reference on its pages; pages held by no
        other sequence (or prefix-cache node) return to the pool."""
        self._require(seq_id)
        pages = self._tables.pop(seq_id)
        del self._lens[seq_id]
        self.decref(pages)

    @_locked
    def seq_len(self, seq_id) -> int:
        self._require(seq_id)
        return self._lens[seq_id]

    @_locked
    def table(self, seq_id) -> list:
        self._require(seq_id)
        return list(self._tables[seq_id])

    # ---------------------------------------------- device-facing arrays
    @_locked
    def table_array(self, seq_ids) -> np.ndarray:
        """Dense int32 page-table batch ``[B, max_pages_per_seq]`` for
        the decode kernel; missing/short entries point at the sink."""
        out = np.full((len(seq_ids), self.max_pages_per_seq), self.SINK,
                      dtype=np.int32)
        for i, sid in enumerate(seq_ids):
            pages = self._tables.get(sid)
            if pages:
                out[i, :len(pages)] = pages
        return out

    @_locked
    def lens_array(self, seq_ids) -> np.ndarray:
        """True lengths ``[B]`` int32 (0 for idle/unknown slots)."""
        return np.asarray([self._lens.get(sid, 0) for sid in seq_ids],
                          dtype=np.int32)

    def prefill_rows(self, seq_id, bucket_len: int) -> np.ndarray:
        """Flattened destination rows ``[bucket_len]`` int32 into the
        ``[num_pages*page_size]`` page-row view for a prefill scatter:
        token ``t`` of the sequence lands in its page's slot; padded
        positions (``t >= seq_len``) land in the sink page."""
        return self.chunk_rows(seq_id, 0, bucket_len)

    @_locked
    def chunk_rows(self, seq_id, start: int, bucket_len: int) -> np.ndarray:
        """Destination rows for a prefill *chunk*: positions ``[start,
        start + bucket_len)`` of the sequence map to their page slots;
        positions at or beyond the true length land in the sink page
        (same contract as :meth:`prefill_rows`, which is the
        ``start == 0`` case)."""
        self._require(seq_id)
        ps = self.page_size
        pages = self._tables[seq_id]
        n = self._lens[seq_id]
        rows = np.empty(int(bucket_len), dtype=np.int32)
        for i in range(int(bucket_len)):
            t = int(start) + i
            if t < n:
                rows[i] = pages[t // ps] * ps + (t % ps)
            else:
                rows[i] = self.SINK * ps + (t % ps)
        return rows

    @_locked
    def token_rows(self, seq_id, start: int, stop: int) -> np.ndarray:
        """Flattened page rows (into the ``[num_pages*page_size]`` view)
        for token positions ``[start, stop)`` of a live sequence — the
        gather/scatter index set live migration uses to lift a
        sequence's K/V out of one pool and land it in another. Unlike
        :meth:`chunk_rows` there is no bucket padding: every returned
        row is a real token's slot, so ``len(rows)`` IS the payload
        token count."""
        self._require(seq_id)
        start, stop = int(start), int(stop)
        if not 0 <= start <= stop <= self._lens[seq_id]:
            raise PagePoolError(
                f"token range [{start}, {stop}) outside sequence "
                f"{seq_id!r} length {self._lens[seq_id]}")
        ps = self.page_size
        pages = self._tables[seq_id]
        return np.asarray([pages[t // ps] * ps + (t % ps)
                           for t in range(start, stop)], dtype=np.int32)

    @_locked
    def bind(self, k_pages, v_pages):
        """Rebind the device arrays after a functional update (the jitted
        step returns the new pool contents)."""
        self.k_pages = k_pages
        self.v_pages = v_pages
