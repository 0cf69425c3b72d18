"""GPT serving engine: the GPT adapter over the paged-engine core.

``ServingEngine`` is the deploy-side counterpart of ``GPTHybridTrainStep``.
What every paged engine does lives in :mod:`.engine_core`
(:class:`~.engine_core.PagedEngine`: the page pool and prefix cache, the
AOT bucket set and ``compile_buckets()``, ``status()``, the chunked
prefill's skeleton with its spans, ``release()``), with what the
scheduler may rely on (:class:`~.engine_core.EngineContract`). This
module holds what GPT decides:

- the stacked decode weights (:func:`~paddle_tpu.models.gpt.
  stack_gpt_weights`, shared with ``GPTGenerator``), optionally
  weight-only int8 (``quantize="int8"``);
- the pure step functions below, module globals that the engine jits in
  ``_build_programs()`` (the static cost model traces them, the lint
  analyzes them, tests rebind them): one **decode** program per batch
  bucket, ONE **chunk** program for every chunk of every prompt, and,
  without ``prefill_chunk``, one one-shot **prefill** program per
  prompt-length bucket (``disaggregated=True``: on a prefill mesh, with
  a KV handoff). The bucket sets are closed at construction: a shape
  outside the set raises instead of silently recompiling
  (``tools/check_program.py --model serving`` proves the scheduler never
  requests one);
- ``decode()``: the host's part of a one-token tick. Everything the
  program needs from the host crosses in ONE int32 array
  (:func:`decode_packed_fn`: last token, length, the call counter and
  the page-table row of every slot); the sampling key is the engine's
  base key, resident on the decode device, folded with that counter
  inside the program, so nothing runs eagerly on the device before the
  launch;
- the prefix cache's copy-on-write boundary page (``_alloc_prompt``) and
  live migration (``export_kv`` / ``begin_`` / ``commit_`` /
  ``abort_kv_import``).

Decode math: one token per live sequence per step. Each layer projects
q/k/v for the new token, scatters k/v into the sequence's current page
slot, then attends over the page table with the Pallas ragged
paged-attention kernel (:mod:`paddle_tpu.kernels.paged_attention`; XLA
reference path on request). The chunk program does the same for a chunk
of one prompt with the ragged-prefill kernel.

Telemetry: every prefill/decode step feeds the metric registry, the
flight recorder, and the anomaly monitor under ``path="serving"`` (see
``observability.instrument``), and per-request timing lands on each
finished :class:`~.scheduler.Request`. Under a device trace the chunked
prefill and the decode are ``RecordEvent`` spans (``engine.prefill_begin``
/ ``prefill_step`` / ``decode``) with ``engine.host_prep`` /
``dispatch`` / ``readback`` inside; ``in_flight`` on them counts the
programs dispatched since the engine's last readback.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..models.gpt import (GPTConfig, _ln, flash_attention_gate, gpt_block,
                          sample_logits, stack_gpt_weights)
from ..kernels.paged_attention import (paged_attention_decode,
                                       paged_attention_reference,
                                       paged_prefill_attention,
                                       ragged_prefill_attention)
from ..profiler.utils import RecordEvent
from .engine_core import (EngineShapeError, PagedEngine, _write_rows,
                          smallest_bucket)

__all__ = ["ServingEngine", "EngineShapeError", "decode_step_fn",
           "decode_packed_fn", "prefill_fn", "chunk_prefill_fn",
           "prefill_kv_fn", "scatter_kv_fn"]


# ---------------------------------------------------------------------------
# pure step functions (single source of truth: the engine jits these, the
# static cost model traces them, the lint analyzes them)
# ---------------------------------------------------------------------------

def _is_quant(w):
    """A weight-only-int8 leaf from ``quantization.export.
    quantize_stacked_gpt_weights``: ``{"q": int8, "s": f32}``."""
    return isinstance(w, dict) and "q" in w


def _mm(expr, x, w, dt):
    """Post-scaled einsum: the int8 weight feeds the matmul directly
    (int8-storage x ``dt``-activation — the convert rides the MXU feed)
    and the per-output-channel scale multiplies the RESULT, which is
    exact because contraction never mixes output channels."""
    if not _is_quant(w):
        return jnp.einsum(expr, x, w)
    y = jnp.einsum(expr, x, w["q"].astype(dt))
    return (y * w["s"].astype(dt)).astype(dt)


def _emb(w, idx, dt):
    """Embedding-row gather with per-row dequantization."""
    if not _is_quant(w):
        return w[idx]
    return (w["q"][idx].astype(dt) * w["s"][idx][..., None].astype(dt))


def _dequant_block(p, dt):
    """Materialize one (per-layer) block's quantized weights back to
    ``dt`` — the prefill path runs the standard ``gpt_block`` on it, one
    layer at a time inside the scan, so only a single layer's float
    weights ever exist transiently. Inside the scan the stacked layer
    dim is already sliced off, so the reduced (contraction) axes are the
    LEADING ``q.ndim - s.ndim`` axes of each leaf."""
    def dq(w):
        if not _is_quant(w):
            return w
        q, s = w["q"], w["s"]
        bshape = (1,) * (q.ndim - s.ndim) + tuple(s.shape)
        return (q.astype(jnp.float32) * s.reshape(bshape)).astype(dt)
    return {k: dq(v) for k, v in p.items()}


def _compute_dtype(params, compute_dtype):
    if compute_dtype is not None:
        return jnp.dtype(compute_dtype)
    wte = params["wte"]
    return wte["s"].dtype if _is_quant(wte) else wte.dtype


def decode_step_fn(params, k_pages, v_pages, tokens, positions, page_table,
                   seq_lens, key, *, eps, temperature, top_k, use_kernel,
                   compute_dtype=None):
    """One continuous-batching decode step: for every (possibly idle)
    batch slot, embed the last token, write its K/V into the slot's
    current page, attend over the page table, and sample the next token.

    ``tokens``/``positions`` ``[B]`` int32 (position = seq_len-1);
    ``page_table`` ``[B, pages_per_seq]``; ``seq_lens`` ``[B]`` (0 =
    idle slot → all writes land in the sink page, output is discarded).
    Returns ``(k_pages, v_pages, next_tokens)``: the pools are the layer
    loop's carry, written at ``(layer, rows)`` and read by the kernel at
    ``layer`` — in place where the caller donates them.

    ``params`` may carry weight-only-int8 leaves (``{"q", "s"}`` from
    ``quantize_stacked_gpt_weights``): the decode matmuls then run the
    int8 weight straight into the einsum (storage stays int8 in HBM —
    decode is weight-bandwidth-bound, so this is the ~2x/4x read win)
    and apply the per-output-channel scale to the result.
    """
    blocks, wte, wpe = params["blocks"], params["wte"], params["wpe"]
    dt = _compute_dtype(params, compute_dtype)
    B = tokens.shape[0]
    ps = k_pages.shape[2]
    pos = jnp.maximum(positions, 0).astype(jnp.int32)
    page_table = page_table.astype(jnp.int32)
    seq_lens = seq_lens.astype(jnp.int32)
    x = _emb(wte, tokens, dt)[:, None, :] + _emb(wpe, pos, dt)[:, None, :]
    x = x.astype(dt)
    # destination page row of the token being decoded (sink for idle)
    rows = (page_table[jnp.arange(B), pos // ps] * ps + pos % ps)
    attend = paged_attention_decode if use_kernel \
        else paged_attention_reference

    def layer(carry, p_l):
        x, kp, vp = carry
        p, l = p_l
        h = _ln(x, p["ln1_w"], p["ln1_b"], eps)
        qkv = _mm("bsh,hknd->bsknd", h, p["wqkv"], dt) + p["bqkv"]
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]  # [B,1,nh,d]
        kp = _write_rows(kp, l, rows, k[:, 0])
        vp = _write_rows(vp, l, rows, v[:, 0])
        attn = attend(q[:, 0], kp, vp, page_table, seq_lens, layer=l)
        o = _mm("bnd,ndh->bh", attn.astype(x.dtype), p["wo"], dt)
        x = x + o[:, None, :] + p["bo"]
        h2 = _ln(x, p["ln2_w"], p["ln2_b"], eps)
        u = jax.nn.gelu(_mm("bsh,hf->bsf", h2, p["w1"], dt) + p["b1"],
                        approximate=True)
        x = x + _mm("bsf,fh->bsh", u, p["w2"], dt) + p["b2"]
        return (x, kp, vp), None

    # the pool rides in the carry; xs are the weights and the layer index
    layers = jnp.arange(k_pages.shape[0], dtype=jnp.int32)
    (x, k_pages, v_pages), _ = jax.lax.scan(
        layer, (x, k_pages, v_pages), (blocks, layers))
    h = _ln(x, params["lnf_w"], params["lnf_b"], eps)
    logits = _mm("bsh,vh->bsv", h, wte, dt)[:, 0]
    nxt = sample_logits(logits, key, temperature, top_k).astype(jnp.int32)
    return k_pages, v_pages, nxt


# columns of a decode tick's packed state ``int32[bucket, 3 + pages]``
_TOKEN, _LEN, _CALL, _TABLE = 0, 1, 2, 3


def decode_packed_fn(step, params, k_pages, v_pages, state, key):
    """The decode program as the engine launches it: ``step`` is
    :func:`decode_step_fn` with its static arguments bound, ``state``
    ``int32[B, 3 + pages_per_seq]`` is all that crosses from the host in
    a tick (a slot's last token, its ``seq_len``, the engine's call
    counter as uint32 bits, its page-table row) and ``key`` the engine's
    base key, which stays on the device. The position is ``seq_len - 1``
    (an idle slot's is clamped to 0 by the step) and the tick's sampling
    key ``fold_in(key, counter)``: the bits the host made eagerly
    before."""
    seq_lens = state[:, _LEN]
    calls = jax.lax.bitcast_convert_type(state[0, _CALL], jnp.uint32)
    return step(params, k_pages, v_pages, state[:, _TOKEN], seq_lens - 1,
                state[:, _TABLE:], seq_lens, jax.random.fold_in(key, calls))


def prefill_fn(params, k_pages, v_pages, ids, true_len, dest_rows, key, *,
               eps, temperature, top_k, use_flash, compute_dtype=None):
    """Prefill one request (batch 1, prompt padded to a bucket length):
    full causal forward capturing per-layer K/V, scatter the true
    tokens' K/V into the allocated pages (padding rows → sink page),
    sample the first output token from position ``true_len - 1``.

    Returns ``(k_pages, v_pages, first_token[1])``.

    Quantized params are dequantized per layer INSIDE the scan (one
    layer of float weights transient at a time), then ride the standard
    ``gpt_block`` — prefill is compute-bound, so int8 storage still
    saves HBM residency without a bespoke kernel path.
    """
    blocks, wte, wpe = params["blocks"], params["wte"], params["wpe"]
    dt = _compute_dtype(params, compute_dtype)
    s = ids.shape[1]
    np_, ps = k_pages.shape[1], k_pages.shape[2]
    h = (_emb(wte, ids, dt) + _emb(wpe, jnp.arange(s), dt)).astype(dt)

    def pre(x, p):
        out, k, v = gpt_block(_dequant_block(p, dt), x, eps,
                              use_flash=use_flash, return_kv=True)
        return out, (k, v)

    h, (ks, vs) = jax.lax.scan(pre, h, blocks)  # ks [L, 1, S, nkv, d]
    L, _, _, nkv, d = ks.shape
    dest_rows = dest_rows.astype(jnp.int32)
    k_pages = k_pages.reshape(L, np_ * ps, nkv, d).at[:, dest_rows].set(
        ks[:, 0]).reshape(k_pages.shape)
    v_pages = v_pages.reshape(L, np_ * ps, nkv, d).at[:, dest_rows].set(
        vs[:, 0]).reshape(v_pages.shape)
    h_last = jax.lax.dynamic_slice_in_dim(
        h, jnp.maximum(true_len - 1, 0), 1, axis=1)
    h_last = _ln(h_last, params["lnf_w"], params["lnf_b"], eps)
    logits = _mm("bsh,vh->bsv", h_last, wte, dt)[:, 0]
    tok = sample_logits(logits, key, temperature, top_k).astype(jnp.int32)
    return k_pages, v_pages, tok


def chunk_prefill_fn(params, k_pages, v_pages, ids, q_offset, chunk_len,
                     page_table, dest_rows, key, *, eps, temperature,
                     top_k, use_kernel=False, compute_dtype=None):
    """Prefill one CHUNK of a prompt (batch 1, ``ids`` padded to the
    engine's chunk length ``C``): embed the chunk at absolute positions
    ``q_offset + i``, scatter its K/V into the sequence's pages
    (``dest_rows``; padding rows → sink), attend over the page table
    with the traced-offset causal rule (row ``i`` sees positions
    ``<= q_offset + i`` — cached prefix pages included, so this one
    program is BOTH the chunked-prefill tick and the prefix-cache
    suffix prefill), and sample a token at local index ``chunk_len-1``
    (only meaningful on the final chunk; earlier chunks' samples are
    discarded by the caller).

    ``q_offset``/``chunk_len`` are traced int32 scalars: every chunk of
    every prompt at every cached-prefix length is the SAME compiled
    program — the chunk shape set stays closed (one signature) and
    serving never recompiles.

    The pools are the layer loop's carry, as in :func:`decode_step_fn`;
    ``use_kernel`` picks the ragged Pallas kernel on the whole pool over
    the XLA dense gather of one layer's pages (the modelable path, and
    the one the ``ragged_prefill`` rewrite rule matches).

    Returns ``(k_pages, v_pages, tok[1])``.
    """
    blocks, wte, wpe = params["blocks"], params["wte"], params["wpe"]
    dt = _compute_dtype(params, compute_dtype)
    C = ids.shape[1]
    q_offset = jnp.asarray(q_offset, jnp.int32)
    chunk_len = jnp.asarray(chunk_len, jnp.int32)
    max_pos = (wpe["q"] if _is_quant(wpe) else wpe).shape[0]
    positions = jnp.minimum(q_offset + jnp.arange(C, dtype=jnp.int32),
                            max_pos - 1)
    x = (_emb(wte, ids, dt) + _emb(wpe, positions, dt)[None]).astype(dt)
    rows = dest_rows.astype(jnp.int32)
    page_table = page_table.astype(jnp.int32)
    attend = ragged_prefill_attention if use_kernel \
        else paged_prefill_attention

    def layer(carry, p_l):
        x, kp, vp = carry
        p, l = p_l
        h = _ln(x, p["ln1_w"], p["ln1_b"], eps)
        qkv = _mm("bsh,hknd->bsknd", h, p["wqkv"], dt) + p["bqkv"]
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]  # [1,C,nh,d]
        kp = _write_rows(kp, l, rows, k[0])
        vp = _write_rows(vp, l, rows, v[0])
        attn = attend(q, kp, vp, page_table, q_offset, layer=l)
        o = _mm("bsnd,ndh->bsh", attn.astype(x.dtype), p["wo"], dt)
        x = x + o + p["bo"]
        h2 = _ln(x, p["ln2_w"], p["ln2_b"], eps)
        u = jax.nn.gelu(_mm("bsh,hf->bsf", h2, p["w1"], dt) + p["b1"],
                        approximate=True)
        x = x + _mm("bsf,fh->bsh", u, p["w2"], dt) + p["b2"]
        return (x, kp, vp), None

    # the pool rides in the carry; xs are the weights and the layer index
    layers = jnp.arange(k_pages.shape[0], dtype=jnp.int32)
    (x, k_pages, v_pages), _ = jax.lax.scan(
        layer, (x, k_pages, v_pages), (blocks, layers))
    h_last = jax.lax.dynamic_slice_in_dim(
        x, jnp.maximum(chunk_len - 1, 0), 1, axis=1)
    h_last = _ln(h_last, params["lnf_w"], params["lnf_b"], eps)
    logits = _mm("bsh,vh->bsv", h_last, wte, dt)[:, 0]
    tok = sample_logits(logits, key, temperature, top_k).astype(jnp.int32)
    return k_pages, v_pages, tok


def prefill_kv_fn(params, ids, true_len, key, *, eps, temperature, top_k,
                  use_flash, compute_dtype=None):
    """Disaggregated-mode prefill: the full causal forward of
    :func:`prefill_fn`, but returning the per-layer K/V **dense**
    (``[L, S, nkv, d]``) instead of scattering into a local page pool —
    the dense tensors are the explicit KV handoff payload shipped from
    the prefill mesh to the decode mesh, where :func:`scatter_kv_fn`
    lands them in the decode-side pool. Returns ``(ks, vs, tok[1])``."""
    blocks, wte = params["blocks"], params["wte"]
    dt = _compute_dtype(params, compute_dtype)
    s = ids.shape[1]
    h = (_emb(wte, ids, dt)
         + _emb(params["wpe"], jnp.arange(s), dt)).astype(dt)

    def pre(x, p):
        out, k, v = gpt_block(_dequant_block(p, dt), x, eps,
                              use_flash=use_flash, return_kv=True)
        return out, (k, v)

    h, (ks, vs) = jax.lax.scan(pre, h, blocks)  # [L, 1, S, nkv, d]
    h_last = jax.lax.dynamic_slice_in_dim(
        h, jnp.maximum(true_len - 1, 0), 1, axis=1)
    h_last = _ln(h_last, params["lnf_w"], params["lnf_b"], eps)
    logits = _mm("bsh,vh->bsv", h_last, wte, dt)[:, 0]
    tok = sample_logits(logits, key, temperature, top_k).astype(jnp.int32)
    return ks[:, 0], vs[:, 0], tok


def scatter_kv_fn(k_pages, v_pages, ks, vs, dest_rows):
    """Decode-side landing of a disaggregated KV handoff: scatter the
    transferred dense K/V (``[L, S, nkv, d]``) into the decode pool's
    pages at ``dest_rows`` (padding rows → sink). Pages are donated on
    TPU — the handoff updates the pool in place."""
    L, _, nkv, d = ks.shape
    np_, ps = k_pages.shape[1], k_pages.shape[2]
    rows = dest_rows.astype(jnp.int32)
    k_pages = k_pages.reshape(L, np_ * ps, nkv, d).at[:, rows].set(
        ks.astype(k_pages.dtype)).reshape(k_pages.shape)
    v_pages = v_pages.reshape(L, np_ * ps, nkv, d).at[:, rows].set(
        vs.astype(v_pages.dtype)).reshape(v_pages.shape)
    return k_pages, v_pages


def default_prefill_buckets(page_size, max_seq_len):
    """Doubling page-multiple prompt buckets covering max_seq_len —
    small, closed, and every bucket is a whole number of pages."""
    buckets, b = [], max(int(page_size), 1)
    while b < max_seq_len:
        buckets.append(b)
        b *= 2
    buckets.append(int(max_seq_len))
    return tuple(sorted(set(buckets)))


# ---------------------------------------------------------------------------

class ServingEngine(PagedEngine):
    """See module docstring. ``model`` is a built GPT model (or anything
    ``stack_gpt_weights`` accepts); ``config`` its :class:`GPTConfig`
    (derived from the model when omitted)."""

    can_migrate = True      # export_kv / begin_ / commit_ / abort_kv_import

    def __init__(self, model, config=None, *, page_size=16, num_pages=None,
                 max_seq_len=None, decode_buckets=(1, 2, 4, 8),
                 prefill_buckets=None, temperature=0.0, top_k=0, seed=0,
                 use_flash=None, use_kernel=True, aot=True, quantize=None,
                 prefill_chunk=None, prefix_cache=False,
                 disaggregated=False, prefill_devices=None,
                 decode_devices=None, autofuse=None):
        gpt = model.gpt if hasattr(model, "gpt") else model
        self.cfg: GPTConfig = config or gpt.config
        cfg = self.cfg
        params = stack_gpt_weights(model)
        # serving-side weight dtype: quantize="int8" stores every decode
        # matmul weight as int8 + per-channel f32 scales (the
        # quantization/export.py deploy scheme routed into the engine) —
        # HBM-resident weights shrink ~4x (f32) / ~2x (bf16) and the
        # memory-bound decode loop streams int8
        compute_dtype = params["wte"].dtype
        self.quantize = quantize
        if quantize is not None:
            if quantize != "int8":
                raise ValueError(
                    f"quantize={quantize!r}: only 'int8' is supported")
            from ..quantization.export import quantize_stacked_gpt_weights
            params = quantize_stacked_gpt_weights(params)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.use_kernel = bool(use_kernel)
        self._use_flash = use_flash
        max_seq_len = int(max_seq_len or cfg.max_position_embeddings)
        self.prefill_buckets = tuple(sorted(set(
            int(b) for b in (prefill_buckets or default_prefill_buckets(
                page_size, max_seq_len)))))
        if self.prefill_buckets[-1] < max_seq_len:
            raise ValueError("largest prefill bucket must cover "
                             "max_seq_len")
        # prefix sharing needs the offset-aware chunk program (a suffix
        # prefill starts mid-prompt), so prefix_cache implies chunking
        if prefix_cache and prefill_chunk is None:
            prefill_chunk = min(8 * page_size, self.prefill_buckets[-1])
        # ---- disaggregated prefill/decode (opt-in mode) -------------
        self.disaggregated = bool(disaggregated)
        if self.disaggregated and (prefill_chunk is not None
                                   or prefix_cache):
            raise ValueError(
                "disaggregated=True runs whole-prompt prefills on a "
                "separate mesh; combine it with prefix_cache/"
                "prefill_chunk in a later PR, not here")
        super().__init__(
            params, num_layers=cfg.num_layers, num_kv_heads=cfg.num_heads,
            head_dim=cfg.head_dim, dtype=compute_dtype,
            max_positions=cfg.max_position_embeddings, page_size=page_size,
            num_pages=num_pages, max_seq_len=max_seq_len,
            decode_buckets=decode_buckets, prefill_chunk=prefill_chunk,
            prefix_cache=prefix_cache)
        self._key = jax.random.key(int(seed))
        self._calls = 0
        # each sequence's pending (last sampled, not yet cached) token,
        # so scheduler and engine agree on what decodes next
        self._last_token: dict = {}
        self.kv_transfer_bytes = 0
        self.kv_transfers = 0
        # fleet live migration (export_kv / commit_kv_import): sequences
        # moved in/out of this engine and the true K/V payload bytes
        self.kv_migrations_in = 0
        self.kv_migrations_out = 0
        self.kv_migration_bytes = 0
        self._kv_import: dict = {}     # seq_id -> staged import state
        self._prefill_device = self._decode_device = None
        if self.disaggregated:
            devs = list(jax.devices())
            self._prefill_device = (list(prefill_devices)[0]
                                    if prefill_devices else devs[0])
            self._decode_device = (list(decode_devices)[0]
                                   if decode_devices
                                   else devs[-1 if len(devs) > 1 else 0])
            # weights live on BOTH meshes (replicated at init — the
            # per-request wire traffic is only the KV handoff); the
            # pool and decode programs are committed to the decode mesh
            self._prefill_params = jax.device_put(self.params,
                                                  self._prefill_device)
            self.params = jax.device_put(self.params, self._decode_device)
            self.pool.bind(
                jax.device_put(self.pool.k_pages, self._decode_device),
                jax.device_put(self.pool.v_pages, self._decode_device))
        # auto-fusion: rewrite the decode/chunk programs before jit so
        # PTCS004 glue chains (int8 dequant matmuls; with
        # use_kernel=False the chunk program's dense page gather)
        # compile as Pallas kernels; None defers to the
        # PADDLE_NO_AUTOFUSE env gate
        if autofuse is None:
            from ..analysis.rewrite import autofuse_enabled
            autofuse = autofuse_enabled()
        self.autofuse = bool(autofuse)
        # the base key where the decode programs run: a resident
        # argument, folded with the call counter inside the program
        self._decode_key = jax.device_put(
            self._key, next(iter(self.pool.k_pages.devices())))
        self._build_programs()
        if aot:
            self.compile_buckets()

    # ------------------------------------------------------------- build
    @classmethod
    def from_checkpoint(cls, path, config: GPTConfig, **kw):
        """checkpoint-load → engine: ``path`` is a ``paddle.save``d GPT
        state dict (``GPTForPretraining`` or bare ``GPTModel`` keys).
        ``quantize="int8"`` serves the checkpoint with weight-only-int8
        decode matmuls (per-channel scales, kernel==reference parity)."""
        from ..framework.io import load as paddle_load
        from ..models.gpt import GPTForPretraining, GPTModel
        state = paddle_load(path)
        model = GPTForPretraining(GPTModel(config))
        target = model
        if not any(k.startswith("gpt.") for k in state):
            target = model.gpt
        target.set_state_dict(state)
        return cls(model, config, **kw)

    def _build_programs(self):
        """(Re)make the jitted programs from this module's step
        functions as they stand."""
        cfg = self.cfg
        # donation lets XLA update the pool in place on TPU; the CPU
        # backend can't donate and would warn on every step
        donate = jax.default_backend() != "cpu"
        pools = (1, 2) if donate else ()
        kw = dict(eps=cfg.layer_norm_epsilon, temperature=self.temperature,
                  top_k=self.top_k,
                  compute_dtype=str(np.dtype(self.compute_dtype)))
        if self.autofuse:
            from ..analysis.rewrite import autofuse as _fuse
        else:
            def _fuse(fn, label):
                return fn

        def flash(sb):
            return flash_attention_gate(sb, cfg.head_dim, self._use_flash)
        self._decode_jit = jax.jit(
            _fuse(functools.partial(
                decode_packed_fn,
                functools.partial(decode_step_fn,
                                  use_kernel=self.use_kernel, **kw)),
                "serving.decode_step"),
            donate_argnums=pools)
        self._prefill_jit = {
            sb: jax.jit(functools.partial(prefill_fn, use_flash=flash(sb),
                                          **kw),
                        donate_argnums=pools)
            for sb in self.prefill_buckets}
        self._chunk_jit = jax.jit(
            _fuse(functools.partial(chunk_prefill_fn,
                                    use_kernel=self.use_kernel, **kw),
                  "serving.chunk_prefill"),
            donate_argnums=pools) \
            if self.prefill_chunk is not None else None
        # COW boundary copy: one fixed-shape program per pool (donated
        # on TPU so the copy is page-local, not a pool-sized shuffle)
        self._copy_page_jit = jax.jit(
            lambda kp, vp, src, dst: (
                kp.at[:, dst].set(kp[:, src]),
                vp.at[:, dst].set(vp[:, src])),
            donate_argnums=(0, 1) if donate else ())
        if self.disaggregated:
            self._prefill_kv_jit = {
                sb: jax.jit(functools.partial(prefill_kv_fn,
                                              use_flash=flash(sb), **kw))
                for sb in self.prefill_buckets}
            self._scatter_jit = jax.jit(
                scatter_kv_fn, donate_argnums=(0, 1) if donate else ())
        self._decode_exe, self._chunk_exe = {}, None
        self._prefill_exe: dict = {}
        self._scatter_exe: dict = {}
        self._copy_exe = None

    def _aval(self, shape, dtype, side="decode"):
        """ShapeDtypeStruct for AOT lowering — carrying an explicit
        single-device sharding in disaggregated mode, so each side's
        executables compile for THEIR mesh (not the default device;
        committed runtime arrays would otherwise mismatch)."""
        if not self.disaggregated:
            return jax.ShapeDtypeStruct(shape, dtype)
        from jax.sharding import SingleDeviceSharding
        dev = self._prefill_device if side == "prefill" \
            else self._decode_device
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=SingleDeviceSharding(dev))

    def _to_decode(self, x):
        """Commit a host array to the decode mesh in disaggregated
        mode (no-op otherwise — default placement already matches)."""
        if not self.disaggregated:
            return jnp.asarray(x)
        return jax.device_put(x, self._decode_device)

    def _key_aval(self):
        return self._aval(self._key.shape, self._key.dtype)

    def _decode_avals(self, b):
        return (self._aval((b, _TABLE + self.pool.max_pages_per_seq),
                           jnp.int32), self._key_aval())

    def _chunk_extra_avals(self):
        return (self._key_aval(),)

    def _chunk_extra_args(self, seq_id, final):
        return (self._next_key(),)

    def _compile_more(self, params_avals, kp):
        """The one-shot prefill programs of a bucketed or disaggregated
        engine (the chunk program REPLACES the per-bucket set: one
        executable serves every prompt length / chunk offset) and the
        prefix cache's boundary copy."""
        p, i32 = self.pool, jnp.int32
        if self.prefill_chunk is None and self.disaggregated:
            # per-side bucket sets: prefill programs compile FOR the
            # prefill mesh, the scatter (handoff landing) + decode
            # programs FOR the decode mesh — the avals carry each
            # side's device so the executables match the committed
            # runtime arrays on any topology
            L, nkv, d = (self.cfg.num_layers, p.num_kv_heads, p.head_dim)
            pa = lambda s, dt: self._aval(s, dt, side="prefill")
            for sb in self.prefill_buckets:
                if sb in self._prefill_exe:
                    continue
                self._prefill_exe[sb] = self._prefill_kv_jit[sb].lower(
                    jax.tree_util.tree_map(
                        lambda a: pa(a.shape, a.dtype),
                        self._prefill_params),
                    pa((1, sb), i32), pa((), i32),
                    pa(self._key.shape, self._key.dtype)).compile()
                kv = self._aval((L, sb, nkv, d), p.k_pages.dtype)
                self._scatter_exe[sb] = self._scatter_jit.lower(
                    kp, kp, kv, kv, self._aval((sb,), i32)).compile()
        elif self.prefill_chunk is None:
            for sb in self.prefill_buckets:
                if sb in self._prefill_exe:
                    continue
                self._prefill_exe[sb] = self._prefill_jit[sb].lower(
                    params_avals, kp, kp,
                    jax.ShapeDtypeStruct((1, sb), i32),
                    jax.ShapeDtypeStruct((), i32),
                    jax.ShapeDtypeStruct((sb,), i32),
                    self._key_aval()).compile()
        if self.prefix_cache is not None and self._copy_exe is None:
            # the COW boundary copy is a serving-time program too: AOT
            # it so the FIRST mid-page cache hit never compiles inside
            # a tick (same zero-retrace contract as the bucket set)
            self._copy_exe = self._copy_page_jit.lower(
                kp, kp, jax.ShapeDtypeStruct((), i32),
                jax.ShapeDtypeStruct((), i32)).compile()

    def prefill_signatures(self) -> set:
        """The closed set of prefill-side program shapes for THIS
        engine mode: ``("chunk", C, pages_per_seq)`` (one program) when
        chunked, ``("disagg", sb)`` + ``("scatter", sb)`` per bucket
        when disaggregated, else the classic ``(1, sb)`` bucket set —
        what the recompile lint checks the scheduler against."""
        if self.prefill_chunk is not None:
            return super().prefill_signatures()
        if self.disaggregated:
            return {("disagg", sb) for sb in self.prefill_buckets} \
                | {("scatter", sb) for sb in self.prefill_buckets}
        return {(1, sb) for sb in self.prefill_buckets}

    def status(self) -> dict:
        """The core's snapshot plus the GPT engine's modes: quantization,
        auto-fusion, the one-shot buckets, disaggregation and migration
        state."""
        st = super().status()
        st.update(quantize=self.quantize, autofuse=self.autofuse,
                  prefill_buckets=list(self.prefill_buckets))
        st["aot_programs"] += (len(self._prefill_exe)
                               + len(self._scatter_exe)
                               + (self._copy_exe is not None))
        if self.disaggregated:
            st["disaggregated"] = {
                "prefill_device": str(self._prefill_device),
                "decode_device": str(self._decode_device),
                "kv_transfers": self.kv_transfers,
                "kv_transfer_mb": round(
                    self.kv_transfer_bytes / 2 ** 20, 2),
            }
        if self.kv_migrations_in or self.kv_migrations_out:
            st["migration"] = {
                "migrations_in": self.kv_migrations_in,
                "migrations_out": self.kv_migrations_out,
                "kv_bytes": self.kv_migration_bytes,
            }
        return st

    # ------------------------------------------------------------ lookup
    def _next_key(self):
        self._calls += 1
        return jax.random.fold_in(self._key, self._calls)

    def prefill_bucket(self, prompt_len: int) -> int:
        return smallest_bucket(self.prefill_buckets, prompt_len,
                               "prompt tokens")

    def _decode_fn(self, bucket):
        if bucket in self._decode_exe:
            return self._decode_exe[bucket]
        if bucket not in self.decode_buckets:
            raise EngineShapeError(
                f"decode batch {bucket} is not an AOT bucket "
                f"{self.decode_buckets}")
        return self._decode_jit  # aot=False: jit caches per bucket shape

    def _prefill_fn(self, bucket):
        if bucket in self._prefill_exe:
            return self._prefill_exe[bucket]
        if bucket not in self.prefill_buckets:
            raise EngineShapeError(
                f"prefill length {bucket} is not an AOT bucket "
                f"{self.prefill_buckets}")
        return self._prefill_jit[bucket]

    # ------------------------------------------------------------- steps
    def _check_prompt_room(self, prompt_ids) -> np.ndarray:
        prompt = np.asarray(prompt_ids, dtype=np.int32).reshape(-1)
        n = int(prompt.shape[0])
        if n + 1 > self.max_seq_len:
            raise EngineShapeError(
                f"prompt of {n} tokens leaves no room to decode within "
                f"max_seq_len {self.max_seq_len}")
        return prompt

    def prefill(self, seq_id, prompt_ids) -> int:
        """Allocate pages for ``prompt_ids``, run the prefill (bucketed
        one-shot, chunked, or disaggregated — whatever this engine
        mode compiled), return the first generated token (int)."""
        if self.prefill_chunk is not None:
            self.prefill_begin(seq_id, prompt_ids)
            while True:
                _, done, tok = self.prefill_step(seq_id)
                if done:
                    return tok
        prompt = self._check_prompt_room(prompt_ids)
        n = int(prompt.shape[0])
        sb = self.prefill_bucket(n)
        if self.disaggregated:
            return self._prefill_disaggregated(seq_id, prompt, sb)
        self.pool.alloc(seq_id, n)
        ids = np.zeros((1, sb), np.int32)
        ids[0, :n] = prompt
        rows = self.pool.prefill_rows(seq_id, sb)
        kp, vp, tok = self._prefill_fn(sb)(
            self.params, self.pool.k_pages, self.pool.v_pages,
            jnp.asarray(ids), jnp.asarray(np.int32(n)),
            jnp.asarray(rows), self._next_key())
        self.pool.bind(kp, vp)
        tok = int(np.asarray(tok)[0])
        self._in_flight = 0
        self._last_token[seq_id] = tok
        return tok

    def _prefill_disaggregated(self, seq_id, prompt, sb) -> int:
        """Prefill on the prefill mesh, explicit KV handoff, scatter
        into the decode-side pool — TPLA's split, each side keeping its
        own parallelism and bucket set."""
        n = int(prompt.shape[0])
        ids = np.zeros((1, sb), np.int32)
        ids[0, :n] = prompt
        fn = self._prefill_exe.get(sb) or self._prefill_kv_jit[sb]
        put_p = functools.partial(jax.device_put,
                                  device=self._prefill_device)
        ks, vs, tok = fn(self._prefill_params,
                         put_p(jnp.asarray(ids)),
                         put_p(jnp.asarray(np.int32(n))),
                         put_p(self._next_key()))
        # the handoff: dense prompt K/V crosses meshes exactly once;
        # book the TRUE payload (the prompt's n positions), not the
        # bucket-padded tensor — predict.py prices prompt_len and the
        # measured/predicted reconciliation must compare like to like
        ks, vs = jax.device_put((ks, vs), self._decode_device)
        per_pos = int(ks.nbytes) // sb
        self.kv_transfers += 1
        self.kv_transfer_bytes += 2 * per_pos * n
        self.pool.alloc(seq_id, n)
        rows = self.pool.prefill_rows(seq_id, sb)
        scatter = self._scatter_exe.get(sb) or self._scatter_jit
        kp, vp = scatter(self.pool.k_pages, self.pool.v_pages, ks, vs,
                         self._to_decode(rows))
        self.pool.bind(kp, vp)
        self._in_flight += 1    # the scatter: the token's readback
        #                         waits for the prefill side alone
        tok = int(np.asarray(tok)[0])
        self._last_token[seq_id] = tok
        return tok

    # ----------------------------------------- chunked / cached prefill
    def prefill_begin(self, seq_id, prompt_ids) -> int:
        """Start a chunked prefill: match the prefix cache (longest
        cached prefix maps straight into the new page table; a
        mid-page divergence copies the boundary page — COW), allocate
        the remaining pages, and queue the suffix for
        :meth:`prefill_step` ticks. Returns the cached prefix length
        (0 without a cache or on a miss)."""
        if self.prefill_chunk is None:
            raise EngineShapeError(
                "prefill_begin requires a chunked engine "
                "(prefill_chunk=...)")
        prompt = self._check_prompt_room(prompt_ids)
        return self._begin_prefill(seq_id, prompt, int(prompt.shape[0]))

    def _alloc_prompt(self, seq_id, prompt) -> int:
        """The core's whole pages, and the page a hit ends inside: the
        boundary page is copied (COW) so the sequence may write the rest
        of it."""
        if self.prefix_cache is None:
            return super()._alloc_prompt(seq_id, prompt)
        n = int(prompt.shape[0])
        cache = self.prefix_cache
        with RecordEvent("prefix.match"):
            nodes, boundary, cached_len = cache.match(prompt)
            pages = cache.map_into(seq_id, nodes, boundary)
        cow = None
        with RecordEvent("pool.alloc", cow=boundary is not None):
            try:
                if boundary is not None:
                    cow = self.pool._take_page()
                    copy = self._copy_exe if self._copy_exe is not None \
                        else self._copy_page_jit
                    kp, vp = copy(
                        self.pool.k_pages, self.pool.v_pages,
                        jnp.asarray(np.int32(boundary[0].page)),
                        jnp.asarray(np.int32(cow)))
                    self.pool.bind(kp, vp)
                    self._in_flight += 1
                    pages = pages + [cow]
                self.pool.alloc_prefixed(seq_id, n, pages, cached_len)
            except Exception:
                # shared pages stay cache-owned (map_into only pinned
                # them); only the transient COW page needs returning
                cache.release(seq_id)
                if cow is not None:
                    self.pool.decref([cow])
                raise
            if cow is not None:
                # alloc_prefixed took the sequence's reference on the
                # COW page; drop the engine's transient one (net: the
                # copy is private to the sequence)
                self.pool.decref([cow])
        return cached_len

    def _chunk_read(self, seq_id, tok):
        """A prompt's last chunk yields the first token."""
        tok = int(np.asarray(tok)[0])
        self._last_token[seq_id] = tok
        return tok

    def decode(self, seq_ids, bucket=None):
        """One decode step for ``seq_ids`` (each already holding its new
        position via ``pool.extend``), padded to ``bucket`` idle slots.
        Returns the next token per live sequence (list of ints)."""
        n = len(seq_ids)
        bucket = self.decode_bucket(n) if bucket is None else bucket
        if n > bucket:
            raise EngineShapeError(f"{n} sequences > bucket {bucket}")
        with RecordEvent("engine.decode", n=n, bucket=bucket,
                         in_flight=self._in_flight):
            # h2d: host arrays sent in this call
            with RecordEvent("engine.host_prep", h2d=1):
                slots = list(seq_ids) + [None] * (bucket - n)
                self._calls += 1    # as _next_key(): one stream of keys
                state = np.empty(
                    (bucket, _TABLE + self.pool.max_pages_per_seq),
                    np.int32)
                state[:, _TOKEN] = [self._last_token.get(sid, 0)
                                    for sid in slots]
                state[:, _LEN] = self.pool.lens_array(slots)
                state[:, _CALL] = np.uint32(self._calls).astype(np.int32)
                state[:, _TABLE:] = self.pool.table_array(slots)
                state = self._to_decode(state)
            with RecordEvent("engine.dispatch"):
                kp, vp, nxt = self._decode_fn(bucket)(
                    self.params, self.pool.k_pages, self.pool.v_pages,
                    state, self._decode_key)
                self.pool.bind(kp, vp)
            self._in_flight += 1
            with RecordEvent("engine.readback",
                             in_flight=self._in_flight):
                out = [int(t) for t in np.asarray(nxt)[:n]]
                for sid, t in zip(seq_ids, out):
                    self._last_token[sid] = t
            self._in_flight = 0
        return out

    def _forget(self, seq_id):
        self._last_token.pop(seq_id, None)

    # -------------------------------------------------- live migration
    # Host-staged KV hand-off between engines (fleet live migration):
    # the source gathers a sequence's valid K/V rows into dense arrays,
    # the wire carries them, and the destination scatters them into its
    # own pool behind a fresh page table. The destination reuses any
    # radix-cache prefix it already holds (full pages only — the
    # mid-page COW boundary is not worth a device copy on this path),
    # so only the uncached suffix ever crosses the wire.

    def export_kv(self, seq_id, start: int = 0):
        """Gather K/V for token positions ``[start, seq_len)`` of a live
        sequence into dense host arrays ``[L, n, num_kv_heads,
        head_dim]`` (one pair). ``seq_len`` covers exactly the positions
        whose K/V entered the pool — the final sampled token's K/V has
        not, and must travel as ``_last_token`` metadata instead."""
        pool = self.pool
        n = pool.seq_len(seq_id)
        rows = pool.token_rows(seq_id, start, n)
        shape = pool.k_pages.shape    # [L, P, ps, nkv, d]
        flat = (shape[0], shape[1] * shape[2], shape[3], shape[4])
        k = np.asarray(pool.k_pages).reshape(flat)[:, rows].copy()
        v = np.asarray(pool.v_pages).reshape(flat)[:, rows].copy()
        return k, v

    def begin_kv_import(self, seq_id, token_ids) -> int:
        """Destination side, step 1: match ``token_ids`` (the tokens
        whose K/V the source would send) against this engine's prefix
        cache and pin the matched FULL pages under ``seq_id``. Returns
        the cached prefix length (page-aligned; 0 without a cache or on
        a miss) — the source then exports only ``[cached_len, n)``.
        Must be balanced by :meth:`commit_kv_import` or
        :meth:`abort_kv_import`."""
        if seq_id in self._kv_import:
            raise EngineShapeError(
                f"sequence {seq_id!r} already has a staged KV import")
        prompt = np.asarray(token_ids, np.int32).reshape(-1)
        pages: list = []
        cached_len = 0
        if self.prefix_cache is not None:
            nodes, _boundary, _ = self.prefix_cache.match(prompt)
            # full pages only: a mid-page boundary would need a COW copy
            # before any suffix row lands next to shared content
            cached_len = len(nodes) * self.pool.page_size
            pages = self.prefix_cache.map_into(seq_id, nodes, None)
        else:
            self.pool.note_prefix_lookup(0)
        self._kv_import[seq_id] = {"pages": pages,
                                   "cached_len": cached_len}
        return cached_len

    def commit_kv_import(self, seq_id, total_len: int, k, v,
                         last_token: int):
        """Destination side, step 2: allocate the page table (cached
        prefix pages + fresh suffix pages), scatter the transferred
        suffix K/V into the pool rows, and arm ``_last_token`` so the
        next decode step resumes token-exact. ``k``/``v`` are the
        source's :meth:`export_kv` output for ``[cached_len,
        total_len)``. On any failure the staged cache pins are released
        and the pool is left untouched."""
        st = self._kv_import.pop(seq_id)
        cached_len = st["cached_len"]
        total_len = int(total_len)
        k = np.asarray(k)
        v = np.asarray(v)
        if k.shape != v.shape or k.shape[1] != total_len - cached_len:
            if self.prefix_cache is not None:
                self.prefix_cache.release(seq_id)
            raise EngineShapeError(
                f"migration payload shape {k.shape} does not cover "
                f"tokens [{cached_len}, {total_len})")
        try:
            self.pool.alloc_prefixed(seq_id, total_len, st["pages"],
                                     cached_len)
        except Exception:
            if self.prefix_cache is not None:
                self.prefix_cache.release(seq_id)
            raise
        rows = self.pool.token_rows(seq_id, cached_len, total_len)
        shape = self.pool.k_pages.shape
        flat = (shape[0], shape[1] * shape[2], shape[3], shape[4])
        kp = np.array(self.pool.k_pages).reshape(flat)
        vp = np.array(self.pool.v_pages).reshape(flat)
        kp[:, rows] = k.astype(kp.dtype, copy=False)
        vp[:, rows] = v.astype(vp.dtype, copy=False)
        self.pool.bind(jnp.asarray(kp.reshape(shape)),
                       jnp.asarray(vp.reshape(shape)))
        self._last_token[seq_id] = int(last_token)
        self.kv_migrations_in += 1
        self.kv_migration_bytes += int(k.nbytes) + int(v.nbytes)
        return cached_len

    def abort_kv_import(self, seq_id):
        """Destination side, bail-out: drop a staged import (release
        the cache pins taken by :meth:`begin_kv_import`). Idempotent —
        the source stays authoritative for the sequence."""
        if self._kv_import.pop(seq_id, None) is not None \
                and self.prefix_cache is not None:
            self.prefix_cache.release(seq_id)
