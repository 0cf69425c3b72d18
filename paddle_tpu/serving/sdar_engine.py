"""Block-diffusion serving engine: SDAR-MoE from the page pool.

``SdarServingEngine`` is the SDAR adapter over the paged-engine core
(:mod:`.engine_core`: the pool, the prefix cache, the AOT bucket set,
``status()``, the chunked prefill's skeleton and ``release()`` are
:class:`~.engine_core.PagedEngine`'s), for a model whose step is not one
token. Of the :class:`~.engine_core.EngineContract` it declares
``block_len`` (the scheduler then drives it through ``_block_tick``): its
prefill yields no token, and one ``decode`` call is one *pass* over the
current block of every running sequence, which returns the 0 to
``block_len`` tokens each of them finished. This module holds the step
functions (module globals: ``_build_programs()`` jits them as they
stand), ``decode()`` and the blocks' host state.

Two programs, the pool carried in place as :mod:`.engine_core` sets out:

- :func:`sdar_chunk_prefill_fn`: one chunk (256) of the prompt's whole
  blocks through ``ragged_prefill_attention`` with ``block=block_len``
  (a key is seen if its block is not later) and grouped heads. No head,
  no token.
- :func:`sdar_block_step_fn`, one per decode bucket: for each sequence
  the ``block_len`` positions of its current block. Every layer writes
  the block's K/V rows into the pool and attends the prefix and the
  whole block. Inside a block all positions see the same keys, so the
  block's ``block_len * group`` queries of a KV head go to
  ``paged_attention_decode`` as ONE group against ``seq_len = block
  end``: the GPT engine's kernel, its grid, page walk and masking; a
  group this wide (32 queries) takes that kernel's MXU path, KV head by
  KV head, where a group of one stays on the VPU. Then the head,
  the argmax token and its confidence at every position, and the choice
  of positions to unmask, all on the device; one packed int32 readback a
  pass (tokens, which positions were unmasked, the confidences' bits,
  per-expert assignment counts).

In both programs every layer's two expert products (gate and up, then
down, over the positions' ``num_experts_per_tok`` assignments sorted by
expert) are :func:`~paddle_tpu.kernels.grouped_matmul.grouped_matmul`
against the flat expert stacks, read where they lie at the layer's index;
its row tile follows the program's rows (``status()["expert_product"]``).
``use_kernel=False`` takes the reference paths of both kernels
(``paged_attention_reference``, ``ragged_dot``).

**One program serves both kinds of pass.** A denoising pass's rows are
overwritten by the next pass of the same block, and a commit pass is the
pass whose input has no masked position: it writes the rows of the final
tokens, unmasks nothing, and its logits are unused. So the program takes
no flag; which sequences commit is the engine's host state
(``masked``), never a comparison with the mask id.

The host keeps, per sequence, the block's tokens, which positions are
masked, the pass count, and for every generated position the pass of its
block at which it was unmasked and the confidence (the softmax
probability of its token) that the program read there (handed to the
scheduler with the tokens: the record that the benchmark's reference
replays, so that numbers are compared and not only choices).

Spans: the core's ``engine.prefill_begin`` / ``prefill_step``,
``sched.batch`` (the batch's blocks and the counts below, worked out
before ``engine.decode`` opens: the decode tick's time outside the
``engine.*`` spans, as it was before it had a span), and ``engine.decode``, each with
``engine.host_prep`` / ``dispatch`` / ``readback`` inside (and
``unpack``: the pass's result applied to the blocks); ``engine.decode``
also carries ``commit`` (1 where every live sequence of the pass
commits), ``n_commit``, ``pass`` (the pass index where all live
sequences are at the same one, else -1) and ``unmasked``.

Live migration is refused by name (``can_migrate`` stays False, so the
fleet answers ``engine_unsupported``; the scheduler's
``checkpoint_request`` and ``migratable_rids`` raise
``MigrationUnsupported``: a block in flight has no token-exact checkpoint
yet); cancellation and eviction release a sequence at any pass, and only
committed tokens are published to the prefix cache, whole pages only (a
page of 64 is 16 blocks, so a hit is exact).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.grouped_matmul import row_tile, tile_visits
from ..kernels.paged_attention import (paged_attention_decode,
                                       paged_attention_reference,
                                       paged_prefill_attention,
                                       ragged_prefill_attention)
from ..models import sdar
from ..profiler.utils import RecordEvent
from .engine_core import (EngineShapeError, PagedEngine, _write_rows,
                          wait_for_device)

__all__ = ["SdarServingEngine", "sdar_block_step_fn",
           "sdar_chunk_prefill_fn", "block_attention"]


def block_attention(q, k_pages, v_pages, page_table, seq_lens, layer,
                    use_kernel=True):
    """Attention of whole blocks over the pool: ``q`` ``[B, bl, nh, d]``,
    every position of a block against the same ``seq_lens`` keys (the
    block's end). The ``bl * group`` queries of a KV head ride the decode
    kernel as one group."""
    B, bl, nh, d = q.shape
    nkv = k_pages.shape[-2]
    g = nh // nkv
    grouped = q.reshape(B, bl, nkv, g, d).transpose(0, 2, 1, 3, 4) \
        .reshape(B, nkv * bl * g, d)
    attend = paged_attention_decode if use_kernel \
        else paged_attention_reference
    out = attend(grouped, k_pages, v_pages, page_table, seq_lens,
                 layer=layer)
    return out.reshape(B, nkv, bl, g, d).transpose(0, 2, 1, 3, 4) \
        .reshape(B, bl, nh, d)


def _layers(params, x, k_pages, v_pages, positions, rows, valid, attend,
            cfg, use_kernel):
    """The layer loop of both programs over ``x`` ``[N, H]``: the pool in
    the carry, ``attend(q [N, nh, d], kp, vp, layer)`` the program's own
    attention, the expert products the grouped-matmul kernel where
    ``use_kernel``. Returns ``(x, k_pages, v_pages, load [L, E])``."""
    experts = params["experts"]

    def layer(carry, p_l):
        x, kp, vp = carry
        p, l = p_l
        h = sdar.rms_norm(x, p["ln1"], cfg.rms_norm_eps)
        q, k, v = sdar.attn_qkv(p, h, positions, cfg)
        kp = _write_rows(kp, l, rows, k)
        vp = _write_rows(vp, l, rows, v)
        x = x + sdar.attn_out(p, attend(q, kp, vp, l).astype(x.dtype))
        a = sdar.rms_norm(x, p["ln2"], cfg.rms_norm_eps)
        y, load = sdar.moe_ffn(a, l, p["router"], experts, cfg, valid,
                               use_kernel)
        return (x + y, kp, vp), load

    layers = jnp.arange(k_pages.shape[0], dtype=jnp.int32)
    (x, k_pages, v_pages), load = jax.lax.scan(
        layer, (x, k_pages, v_pages), (params["blocks"], layers))
    return x, k_pages, v_pages, load


def sdar_block_step_fn(params, k_pages, v_pages, state, *, cfg,
                       threshold=None, use_kernel=True,
                       return_logits=False):
    """One pass over the current block of every (possibly idle) slot.

    ``state`` ``[B, 2 * bl + 2 + pages_per_seq]`` int32, one row a slot:
    the block's ``bl`` tokens (the mask id where masked), ``bl`` flags
    (1 = masked), the block's first position, the sequence's length
    including the block (0 = idle slot: its rows land in the sink page),
    and its page table. Returns ``(k_pages, v_pages, out)`` with ``out``
    one int32 vector: the block's tokens after the pass ``[B * bl]``,
    which positions this pass unmasked ``[B * bl]``, the float32 bits of
    every position's confidence ``[B * bl]``, and the live slots'
    assignments per layer and expert ``[L * E]`` (and the logits ``[B,
    bl, V]`` after it where ``return_logits``).
    """
    bl = cfg.block_length
    threshold = cfg.confidence_threshold if threshold is None else threshold
    B = state.shape[0]
    ps = k_pages.shape[2]
    tokens, masked = state[:, :bl], state[:, bl:2 * bl] > 0
    start, seq_lens = state[:, 2 * bl], state[:, 2 * bl + 1]
    page_table = state[:, 2 * bl + 2:]
    pos = start[:, None] + jnp.arange(bl, dtype=jnp.int32)[None]
    rows = (jnp.take_along_axis(page_table, pos // ps, axis=1) * ps
            + pos % ps).reshape(-1)
    valid = jnp.repeat(seq_lens > 0, bl)

    def attend(q, kp, vp, l):
        out = block_attention(q.reshape(B, bl, *q.shape[1:]), kp, vp,
                              page_table, seq_lens, l, use_kernel)
        return out.reshape(q.shape)

    x = params["embed"][tokens.reshape(-1)]
    x, k_pages, v_pages, load = _layers(
        params, x, k_pages, v_pages, pos.reshape(-1), rows, valid, attend,
        cfg, use_kernel)
    logits = sdar.final_logits(params, x, cfg)
    best, conf = sdar.confidence(logits)
    pick = sdar.choose_unmask(conf.reshape(B, bl), masked, threshold,
                              cfg.unmask_per_pass)
    after = jnp.where(pick, best.reshape(B, bl), tokens)
    out = jnp.concatenate([after.reshape(-1),
                           pick.astype(jnp.int32).reshape(-1),
                           jax.lax.bitcast_convert_type(
                               conf.astype(jnp.float32), jnp.int32),
                           load.reshape(-1)])
    if return_logits:
        return k_pages, v_pages, out, logits.reshape(B, bl, -1)
    return k_pages, v_pages, out


def sdar_chunk_prefill_fn(params, k_pages, v_pages, ids, q_offset,
                          chunk_len, page_table, dest_rows, *, cfg,
                          use_kernel=True, return_logits=False):
    """Prefill one chunk of a prompt's whole blocks (batch 1, ``ids``
    ``[1, C]`` padded; ``q_offset`` a multiple of the block): scatter its
    K/V into the sequence's pages (``dest_rows``; padding rows to the
    sink) and attend under the block rule. Returns ``(k_pages, v_pages,
    load [L * E])``: prefill yields no token (and the logits ``[C, V]``
    where ``return_logits``)."""
    C = ids.shape[1]
    q_offset = jnp.asarray(q_offset, jnp.int32)
    positions = q_offset + jnp.arange(C, dtype=jnp.int32)
    valid = jnp.arange(C, dtype=jnp.int32) < chunk_len
    prefill = ragged_prefill_attention if use_kernel \
        else paged_prefill_attention

    def attend(q, kp, vp, l):
        return prefill(q[None], kp, vp, page_table.astype(jnp.int32),
                       q_offset, layer=l, block=cfg.block_length)[0]

    x = params["embed"][ids[0]]
    x, k_pages, v_pages, load = _layers(
        params, x, k_pages, v_pages, positions,
        dest_rows.astype(jnp.int32), valid, attend, cfg, use_kernel)
    if return_logits:
        return k_pages, v_pages, load.reshape(-1), \
            sdar.final_logits(params, x, cfg)
    return k_pages, v_pages, load.reshape(-1)


class _Block:
    """One running sequence's current block, on the host."""
    __slots__ = ("start", "keep", "tokens", "masked", "n_pass",
                 "unmasked_at", "conf_at", "begun")

    def __init__(self, start, head, bl, mask_id):
        self.start = start
        self.keep = len(head)           # leading prompt positions
        self.tokens = np.full(bl, mask_id, np.int32)
        self.tokens[:self.keep] = head
        self.masked = np.arange(bl) >= self.keep
        self.n_pass = 0
        self.unmasked_at = [-1] * bl
        self.conf_at = [0.0] * bl       # the confidence it was unmasked at
        self.begun = False              # its rows are in the pool's length


class SdarServingEngine(PagedEngine):
    """See the module docstring. ``params`` is the stacked layout of
    :func:`paddle_tpu.models.sdar.sdar_weight_shapes` (placed on the
    pool's device here); greedy only."""

    def __init__(self, params, config: sdar.SdarMoeConfig, *, page_size=64,
                 num_pages=None, max_seq_len=None,
                 decode_buckets=(1, 2, 4, 8), prefill_chunk=256,
                 prefix_cache=False, use_kernel=True, aot=True,
                 threshold=None):
        cfg = self.cfg = config
        self.block_len = bl = cfg.block_length
        if page_size % bl or prefill_chunk % page_size:
            raise ValueError(
                f"a page ({page_size}) must hold whole blocks ({bl}) and a "
                f"chunk ({prefill_chunk}) whole pages")
        max_seq_len = int(max_seq_len or cfg.max_position_embeddings)
        if max_seq_len % bl:
            raise ValueError(f"max_seq_len {max_seq_len}: whole blocks")
        self.use_kernel = bool(use_kernel)
        self.threshold = cfg.confidence_threshold if threshold is None \
            else float(threshold)
        super().__init__(
            params, num_layers=cfg.num_hidden_layers,
            num_kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
            dtype=params["embed"].dtype,
            max_positions=cfg.max_position_embeddings, page_size=page_size,
            num_pages=num_pages, max_seq_len=max_seq_len,
            decode_buckets=decode_buckets, prefill_chunk=prefill_chunk,
            prefix_cache=prefix_cache)
        self._blocks: dict = {}         # seq_id -> _Block
        self._pending_load: list = []   # chunk programs' counts, unread
        self.counters = {"passes_denoise": 0, "passes_commit": 0,
                         "passes_mixed": 0, "blocks_committed": 0,
                         "tokens_emitted": 0, "tokens_dropped": 0,
                         "positions_computed": 0, "prefill_chunks": 0}
        self.expert_load = np.zeros(
            (cfg.num_hidden_layers, cfg.num_experts), np.int64)
        self.last_pass_load = None      # [L, E] of the last decode pass
        self.last_chunk_loads = []      # of the chunks last read back
        self._build_programs()
        if aot:
            self.compile_buckets()

    # ------------------------------------------------------------- build
    def _build_programs(self):
        """(Re)make the two jitted programs from the module's step
        functions as they stand."""
        donate = (1, 2) if jax.default_backend() != "cpu" else ()
        self._decode_jit = jax.jit(
            functools.partial(sdar_block_step_fn, cfg=self.cfg,
                              threshold=self.threshold,
                              use_kernel=self.use_kernel),
            donate_argnums=donate)
        self._chunk_jit = jax.jit(
            functools.partial(sdar_chunk_prefill_fn, cfg=self.cfg,
                              use_kernel=self.use_kernel),
            donate_argnums=donate)
        self._decode_exe, self._chunk_exe = {}, None

    @property
    def _state_width(self):
        return 2 * self.block_len + 2 + self.pool.max_pages_per_seq

    def _decode_avals(self, b):
        return (jax.ShapeDtypeStruct((b, self._state_width), jnp.int32),)

    def status(self) -> dict:
        st = super().status()
        st["passes"] = dict(self.counters)
        st["expert_load"] = self.expert_load.tolist()
        if self.use_kernel:
            st["expert_product"] = self._expert_product()
        return st

    def _expert_product(self) -> dict:
        """The grouped product's row tile in each program (its rows are
        the program's positions times ``num_experts_per_tok``) and how
        full the last pass's row-tile visits were: the live sequences'
        assignments over the rows their visits hold, over all layers."""
        E, k = self.cfg.num_experts, self.cfg.num_experts_per_tok
        out = {"row_tile": {
            "decode": {b: row_tile(b * self.block_len * k, E)
                       for b in self.decode_buckets},
            "chunk": row_tile(self.prefill_chunk * k, E)},
            "tile_fill": None}
        load = self.last_pass_load
        if load is not None and load.sum():
            n = int(load[0].sum()) // (self.block_len * k)
            tm = out["row_tile"]["decode"][self.decode_bucket(n)]
            out["tile_fill"] = float(load.sum()) / (
                tile_visits(load, tm) * tm)
        return out

    # ----------------------------------------------------------- prefill
    def prefill_begin(self, seq_id, prompt_ids) -> int:
        """Pages for the prompt's whole blocks (cached whole pages mapped
        in), the first block laid out from the prompt's remaining
        tokens. Returns the cached prefix length."""
        bl = self.block_len
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        n = int(prompt.shape[0])
        n_full = n // bl * bl
        if n_full + bl > self.max_seq_len:
            raise EngineShapeError(
                f"prompt of {n} tokens leaves no room for a block within "
                f"max_seq_len {self.max_seq_len}")
        cached_len = self._begin_prefill(seq_id, prompt[:n_full], n)
        block = _Block(n_full, prompt[n_full:], bl, self.cfg.mask_token_id)
        block.begun = n_full == 0       # its rows were allocated above
        self._blocks[seq_id] = block
        return cached_len

    def _alloc_prompt(self, seq_id, whole) -> int:
        """Whole pages only, as the core maps them: inside a block every
        position's K/V depends on the block's other tokens, so a hit
        that ends inside a page (the GPT engine's copy-on-write
        boundary) would not be exact."""
        if not whole.shape[0]:          # shorter than a block: no prefill
            self.pool.note_prefix_lookup(0)
            self.pool.alloc(seq_id, self.block_len)
            return 0
        return super()._alloc_prompt(seq_id, whole)

    def prefill_step(self, seq_id):
        """One chunk, as the core runs it: ``(tokens processed, done,
        None)``. The last chunk reads back the chunks' expert counts."""
        st = self._chunk_state[seq_id]
        if st["pos"] >= st["n"]:        # nothing to prefill
            del self._chunk_state[seq_id]
            return 0, True, None
        return super().prefill_step(seq_id)

    def _chunk_issued(self, load):
        self._pending_load.append(load)
        self.counters["prefill_chunks"] += 1

    def _chunk_read(self, seq_id, load):
        """Prefill yields no token: what is read is every pending
        chunk's expert counts."""
        self.last_chunk_loads = [
            np.asarray(load).reshape(self.expert_load.shape)
            for load in self._pending_load]
        for load in self.last_chunk_loads:
            self.expert_load += load
        self._pending_load.clear()

    # ------------------------------------------------------------ decode
    def starts_block(self, seq_id) -> bool:
        """Whether the sequence's next pass is the first of a block whose
        rows the pool does not hold yet (the scheduler then extends it by
        ``block_len``)."""
        return not self._blocks[seq_id].begun

    def masked_positions(self, seq_ids) -> int:
        return int(sum(self._blocks[s].masked.sum() for s in seq_ids))

    def _pass_tokens(self, block):
        """The tokens a pass is fed for a block."""
        return block.tokens

    def decode(self, seq_ids, bucket=None):
        """One pass over the current block of ``seq_ids`` (each holding
        its block's rows via ``pool.extend``), padded to ``bucket``.
        Returns, per sequence, ``(tokens, passes, confidences)``: empty
        unless this was the block's commit pass, else the block's
        generated tokens (the prompt's own left out) and for each the
        pass at which it was unmasked and its confidence there."""
        n, bl = len(seq_ids), self.block_len
        bucket = self.decode_bucket(n) if bucket is None else bucket
        if n > bucket:
            raise EngineShapeError(f"{n} sequences > bucket {bucket}")
        with RecordEvent("sched.batch"):
            blocks = [self._blocks[s] for s in seq_ids]
            commits = [not b.masked.any() for b in blocks]
            at = {b.n_pass for b in blocks}
            attrs = {"n": n, "bucket": bucket, "in_flight": self._in_flight,
                     "commit": int(all(commits)), "n_commit": sum(commits),
                     "pass": at.pop() if len(at) == 1 else -1}
        with RecordEvent("engine.decode", **attrs) as ev:
            with RecordEvent("engine.host_prep"):
                state = np.zeros((bucket, self._state_width), np.int32)
                held = self.pool.lens_array(seq_ids)
                for i, (sid, b) in enumerate(zip(seq_ids, blocks)):
                    if held[i] != b.start + bl:
                        raise EngineShapeError(
                            f"sequence {sid!r}: the pool holds {held[i]} "
                            f"rows, its block ends at {b.start + bl}")
                    b.begun = True
                    state[i, :bl] = self._pass_tokens(b)
                    state[i, bl:2 * bl] = b.masked
                    state[i, 2 * bl] = b.start
                    state[i, 2 * bl + 1] = b.start + bl
                state[:, 2 * bl + 2:] = self.pool.table_array(
                    list(seq_ids) + [None] * (bucket - n))
                fn = self._decode_exe.get(bucket, self._decode_jit)
                arg = jnp.asarray(state)
            with RecordEvent("engine.dispatch"):
                kp, vp, out = fn(self.params, self.pool.k_pages,
                                 self.pool.v_pages, arg)
                self.pool.bind(kp, vp)
            self._in_flight += 1
            with RecordEvent("engine.readback",
                             in_flight=self._in_flight):
                wait_for_device(out)
                with RecordEvent("engine.d2h"):
                    out = np.asarray(out)
            self._in_flight = 0
            with RecordEvent("engine.unpack"):
                after = out[:bucket * bl].reshape(bucket, bl)
                picked = out[bucket * bl:2 * bucket * bl].reshape(
                    bucket, bl) > 0
                conf = out[2 * bucket * bl:3 * bucket * bl].view(
                    np.float32).reshape(bucket, bl)
                load = out[3 * bucket * bl:].reshape(self.expert_load.shape)
                self.expert_load += load
                self.last_pass_load = load
                ev.set(unmasked=int(picked[:n].sum()))
                results = [
                    self._advance(b, commit, after[i], picked[i], conf[i])
                    for i, (b, commit) in enumerate(zip(blocks, commits))]
                c = self.counters
                kind = "passes_commit" if all(commits) else \
                    "passes_mixed" if any(commits) else "passes_denoise"
                c[kind] += 1
                c["positions_computed"] += n * bl
                for sid, commit in zip(seq_ids, commits):
                    if commit:
                        c["blocks_committed"] += 1
                        self._blocks[sid] = _Block(
                            self._blocks[sid].start + bl, (), bl,
                            self.cfg.mask_token_id)
        return results

    @staticmethod
    def _advance(block, commit, after, picked, conf):
        """Apply one pass's result to a block; what it yields."""
        if commit:
            keep = block.keep
            return ([int(t) for t in block.tokens[keep:]],
                    block.unmasked_at[keep:], block.conf_at[keep:])
        for i in np.flatnonzero(picked & block.masked):
            block.tokens[i] = after[i]
            block.masked[i] = False
            block.unmasked_at[i] = block.n_pass
            block.conf_at[i] = float(conf[i])
        block.n_pass += 1
        return [], [], []

    def note_emitted(self, emitted: int, dropped: int):
        """The scheduler's count of what a commit's tokens became."""
        self.counters["tokens_emitted"] += emitted
        self.counters["tokens_dropped"] += dropped

    def _forget(self, seq_id):
        self._blocks.pop(seq_id, None)
