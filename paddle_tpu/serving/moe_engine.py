"""MoE serving engine: ERNIE-MoE as a first-class paged-decode workload.

``MoEServingEngine`` is the expert-parallel sibling of
:class:`~.engine.ServingEngine`: stacked ERNIE-MoE weights
(:func:`paddle_tpu.models.ernie.stack_ernie_moe_weights`), the same
:class:`~.kv_pool.PagePool` + Pallas ragged paged-attention decode, and
the same AOT bucket closure (one prefill program per prompt-length
bucket, one decode program per batch bucket; any shape outside the set
raises :class:`~.engine.EngineShapeError` — ``tools/check_program.py
--model serving`` replays the scheduler against this engine's bucket
sets too). What differs is the FFN: every ``moe_every``-th layer routes
tokens through the **fused Pallas MoE dispatch** kernel
(:mod:`paddle_tpu.kernels.moe_dispatch`) inside the decode program —
gate → capacity-clamped scatter → batched expert FFN → fused combine,
one HBM round-trip (``use_fused_moe=False`` swaps in the gather-based
reference, the modelable path :mod:`.predict` prices).

Because dense and MoE layers carry different weight sets, the layer
walk is a static Python loop over per-layer dicts (the static
``kinds`` tuple picks the FFN body), not a scan — program count and
the bucket-closure contract are unchanged.

MoE capacity in serving: every program sizes expert capacity at the
per-expert no-drop bound (``tokens`` — a token's k choices are distinct
experts), so incremental decode is token-for-token equal to eager
full-recompute generation
(:class:`~paddle_tpu.models.ernie.ErnieMoeGenerator` is the asserted
oracle) — a capacity-dropped token would make the two routes diverge.

Greedy decode, the continuous-batching scheduler drives this engine
unchanged (same ``prefill``/``decode``/``release``/``pool`` surface).
"""
from __future__ import annotations

import functools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..models.ernie import (ErnieMoeConfig, ErnieMoeForPretraining,
                            ErnieMoeModel, stack_ernie_moe_weights)
from ..models.gpt import sample_logits
from ..kernels.paged_attention import (paged_attention_decode,
                                       paged_attention_reference)
from ..kernels.moe_dispatch import (fused_moe_combine, fused_moe_dispatch,
                                    reference_moe_combine,
                                    reference_moe_dispatch)
from .engine import EngineShapeError, ServingEngine, default_prefill_buckets
from .kv_pool import PagePool

__all__ = ["MoEServingEngine", "moe_decode_step_fn", "moe_prefill_fn"]


def _eln(x, w, b, eps):
    """LayerNorm matching ``nn.functional.layer_norm`` exactly (var +
    /sqrt — the eager model's numerics, not gpt's rsqrt variant)."""
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.var(x, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def _gelu(x):
    # nn.functional.gelu defaults to the exact (erf) form — jax's
    # default is the tanh approximation, so be explicit
    return jax.nn.gelu(x, approximate=False)


def _moe_ffn(flat, p, *, top_k, use_fused):
    """MoE FFN over flat tokens ``[N, H]``: fused Pallas dispatch →
    batched expert FFN → fused combine (or the gather-based reference).
    Capacity = N — a token's k gate choices are distinct experts, so no
    single expert can receive more than N rows: serving programs never
    drop a token (the incremental == full-recompute parity contract)
    without over-sizing the expert buffers."""
    E = p["ew1"].shape[0]
    N = flat.shape[0]
    C = N  # per-expert no-drop bound (distinct choices per token)
    dispatch = fused_moe_dispatch if use_fused else reference_moe_dispatch
    ei, comb, val, _, _ = dispatch(
        flat, p["gate_w"], p["gate_b"], num_expert=E, capacity=C,
        top_k=top_k, gate_kind="gshard")
    ei = ei.astype(flat.dtype)
    h = _gelu(jnp.einsum("ecm,emh->ech", ei, p["ew1"])
              + p["eb1"][:, None, :])
    eo = jnp.einsum("ech,ehm->ecm", h, p["ew2"]) + p["eb2"][:, None, :]
    combine = fused_moe_combine if use_fused else reference_moe_combine
    return combine(eo.reshape(E * C, flat.shape[-1]), val, comb)


def _attn_proj(x, p, nh, d):
    """q/k/v projections [B, S, H] → [B, S, nh, d] (paddle Linear
    layout: weight [in, out])."""
    B, S, H = x.shape
    q = (x @ p["wq"] + p["bq"]).reshape(B, S, nh, d)
    k = (x @ p["wk"] + p["bk"]).reshape(B, S, nh, d)
    v = (x @ p["wv"] + p["bv"]).reshape(B, S, nh, d)
    return q, k, v


def moe_decode_step_fn(params, k_pages, v_pages, tokens, positions,
                       page_table, seq_lens, key, *, kinds, eps, top_k,
                       temperature, topk_sample, use_kernel,
                       use_fused_moe):
    """One continuous-batching decode step of the ERNIE-MoE stack: embed
    the last token, per layer write its K/V into the slot's page row,
    paged-attend, then the dense or **fused-MoE** FFN (post-LN residual
    blocks exactly as the eager model), finally the MLM/LM head.
    ``kinds`` is the static dense/moe layer sequence. Returns
    ``(k_pages, v_pages, next_tokens)``."""
    B = tokens.shape[0]
    np_, ps = k_pages.shape[1], k_pages.shape[2]
    pos = jnp.maximum(positions, 0).astype(jnp.int32)
    page_table = page_table.astype(jnp.int32)
    seq_lens = seq_lens.astype(jnp.int32)
    x = (params["wte"][tokens] + params["wpe"][pos])[:, None, :]
    x = _eln(x, params["eln_w"], params["eln_b"], eps)
    rows = (page_table[jnp.arange(B), pos // ps] * ps + pos % ps)
    attend = paged_attention_decode if use_kernel \
        else paged_attention_reference

    new_k, new_v = [], []
    for li, (kind, p) in enumerate(zip(kinds, params["layers"])):
        nkv, d = k_pages.shape[3], k_pages.shape[4]
        nh = nkv
        q, k, v = _attn_proj(x, p, nh, d)             # [B, 1, nh, d]
        kp = k_pages[li].reshape(np_ * ps, nkv, d).at[rows].set(
            k[:, 0].astype(k_pages.dtype)).reshape(np_, ps, nkv, d)
        vp = v_pages[li].reshape(np_ * ps, nkv, d).at[rows].set(
            v[:, 0].astype(v_pages.dtype)).reshape(np_, ps, nkv, d)
        new_k.append(kp)
        new_v.append(vp)
        attn = attend(q[:, 0], kp, vp, page_table, seq_lens)
        o = attn.reshape(B, 1, nh * d) @ p["wo"] + p["bo"]
        x = _eln(x + o, p["ln1_w"], p["ln1_b"], eps)
        if kind == "moe":
            y = _moe_ffn(x[:, 0], p, top_k=top_k,
                         use_fused=use_fused_moe)[:, None, :]
        else:
            y = _gelu(x @ p["w1"] + p["b1"]) @ p["w2"] + p["b2"]
        x = _eln(x + y, p["ln2_w"], p["ln2_b"], eps)

    hd = params["head"]
    h = _eln(_gelu(x @ hd["tw"] + hd["tb"]), hd["ln_w"], hd["ln_b"], eps)
    logits = jnp.einsum("bsh,vh->bsv", h, hd["dw"])[:, 0] + hd["db"]
    nxt = sample_logits(logits, key, temperature,
                        topk_sample).astype(jnp.int32)
    return (jnp.stack(new_k), jnp.stack(new_v), nxt)


def moe_prefill_fn(params, k_pages, v_pages, ids, true_len, dest_rows,
                   key, *, kinds, eps, top_k, temperature, topk_sample,
                   use_fused_moe):
    """Prefill one request (batch 1, prompt padded to a bucket length):
    full causal forward through the dense/MoE stack capturing per-layer
    K/V into the allocated page rows, then sample the first token at
    ``true_len - 1``. MoE capacity = bucket_len (the per-expert no-drop
    bound; padded positions route but cannot steal a real token's
    slot)."""
    s = ids.shape[1]
    np_, ps = k_pages.shape[1], k_pages.shape[2]
    positions = jnp.arange(s, dtype=jnp.int32)
    x = (params["wte"][ids] + params["wpe"][positions][None])
    x = _eln(x, params["eln_w"], params["eln_b"], eps)
    rows = dest_rows.astype(jnp.int32)
    causal = jnp.tril(jnp.ones((s, s), bool))[None, None]

    new_k, new_v = [], []
    for li, (kind, p) in enumerate(zip(kinds, params["layers"])):
        nkv, d = k_pages.shape[3], k_pages.shape[4]
        nh = nkv
        q, k, v = _attn_proj(x, p, nh, d)             # [1, s, nh, d]
        kp = k_pages[li].reshape(np_ * ps, nkv, d).at[rows].set(
            k[0].astype(k_pages.dtype)).reshape(np_, ps, nkv, d)
        vp = v_pages[li].reshape(np_ * ps, nkv, d).at[rows].set(
            v[0].astype(v_pages.dtype)).reshape(np_, ps, nkv, d)
        new_k.append(kp)
        new_v.append(vp)
        # dense causal attention over the chunk itself (mirrors
        # _sdpa_ref's numerics: scale 1/sqrt(d), -1e30 mask, f32 softmax)
        logits = jnp.einsum("bsnd,btnd->bnst", q, k) / math.sqrt(d)
        logits = jnp.where(causal, logits,
                           jnp.asarray(-1e30, logits.dtype))
        probs = jax.nn.softmax(logits.astype(jnp.float32),
                               -1).astype(x.dtype)
        attn = jnp.einsum("bnst,btnd->bsnd", probs, v)
        o = attn.reshape(1, s, nh * d) @ p["wo"] + p["bo"]
        x = _eln(x + o, p["ln1_w"], p["ln1_b"], eps)
        if kind == "moe":
            y = _moe_ffn(x[0], p, top_k=top_k,
                         use_fused=use_fused_moe)[None]
        else:
            y = _gelu(x @ p["w1"] + p["b1"]) @ p["w2"] + p["b2"]
        x = _eln(x + y, p["ln2_w"], p["ln2_b"], eps)

    h_last = jax.lax.dynamic_slice_in_dim(
        x, jnp.maximum(true_len - 1, 0), 1, axis=1)
    hd = params["head"]
    h = _eln(_gelu(h_last @ hd["tw"] + hd["tb"]), hd["ln_w"], hd["ln_b"],
             eps)
    logits = jnp.einsum("bsh,vh->bsv", h, hd["dw"])[:, 0] + hd["db"]
    tok = sample_logits(logits, key, temperature,
                        topk_sample).astype(jnp.int32)
    return (jnp.stack(new_k), jnp.stack(new_v), tok)


class MoEServingEngine:
    """See module docstring. ``model`` is a built
    :class:`ErnieMoeForPretraining`; greedy by default."""

    # one bucket-lookup implementation across engines
    prefill_bucket = ServingEngine.prefill_bucket
    decode_bucket = ServingEngine.decode_bucket
    _check_prompt_room = ServingEngine._check_prompt_room
    decode_signatures = ServingEngine.decode_signatures
    _next_key = ServingEngine._next_key

    def __init__(self, model, config: ErnieMoeConfig | None = None, *,
                 page_size=16, num_pages=None, max_seq_len=None,
                 decode_buckets=(1, 2, 4, 8), prefill_buckets=None,
                 temperature=0.0, top_k=0, seed=0, use_kernel=True,
                 use_fused_moe=True, aot=True, autofuse=None):
        if not isinstance(model, ErnieMoeForPretraining):
            raise TypeError("MoEServingEngine needs ErnieMoeForPretraining")
        self.cfg = config or model.ernie.config
        cfg = self.cfg
        self.params, self.kinds = stack_ernie_moe_weights(model)
        self.moe_top_k = int(cfg.top_k)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.use_kernel = bool(use_kernel)
        self.use_fused_moe = bool(use_fused_moe)
        self.prefill_chunk = None      # scheduler probes this (classic)
        max_seq_len = int(max_seq_len or cfg.max_position_embeddings)
        if max_seq_len > cfg.max_position_embeddings:
            raise ValueError("max_seq_len exceeds the position table")
        self.max_seq_len = max_seq_len
        self.decode_buckets = tuple(sorted(set(int(b)
                                               for b in decode_buckets)))
        self.prefill_buckets = tuple(sorted(set(
            int(b) for b in (prefill_buckets or default_prefill_buckets(
                page_size, max_seq_len)))))
        if self.prefill_buckets[-1] < max_seq_len:
            raise ValueError("largest prefill bucket must cover "
                             "max_seq_len")
        pages_per_seq = math.ceil(max_seq_len / page_size)
        if num_pages is None:
            num_pages = self.decode_buckets[-1] * pages_per_seq + 1
        self.pool = PagePool(num_pages, page_size,
                             num_layers=cfg.num_hidden_layers,
                             num_kv_heads=cfg.num_attention_heads,
                             head_dim=cfg.head_dim,
                             dtype=self.params["wte"].dtype,
                             max_seq_len=max_seq_len)
        # the weights live with the pool, where the programs run
        self.params = jax.device_put(
            self.params, next(iter(self.pool.k_pages.devices())))
        self._key = jax.random.key(int(seed))
        self._calls = 0
        self._last_token: dict = {}
        donate = jax.default_backend() != "cpu"
        eps = cfg.layer_norm_eps
        # auto-fusion: rewrite the decode/prefill programs before jit —
        # with use_fused_moe=False the gate+dispatch glue matches the
        # moe_gate_dispatch rule and compiles as the fused Pallas kernel
        # anyway (the rule identifies the gate variant by structure); the
        # already-fused program has no dense [E,C,M] glue, so the rule
        # leaves it alone
        from ..analysis import rewrite as _rewrite
        self.autofuse = (_rewrite.autofuse_enabled() if autofuse is None
                         else bool(autofuse))
        _fuse = ((lambda fn, label: _rewrite.autofuse(fn, label=label))
                 if self.autofuse else (lambda fn, label: fn))
        self._decode_jit = jax.jit(
            _fuse(functools.partial(moe_decode_step_fn, kinds=self.kinds,
                                    eps=eps, top_k=self.moe_top_k,
                                    temperature=self.temperature,
                                    topk_sample=self.top_k,
                                    use_kernel=self.use_kernel,
                                    use_fused_moe=self.use_fused_moe),
                  "serving.moe_decode_step"),
            donate_argnums=(1, 2) if donate else ())
        self._prefill_jit = jax.jit(
            _fuse(functools.partial(moe_prefill_fn, kinds=self.kinds,
                                    eps=eps, top_k=self.moe_top_k,
                                    temperature=self.temperature,
                                    topk_sample=self.top_k,
                                    use_fused_moe=self.use_fused_moe),
                  "serving.moe_prefill"),
            donate_argnums=(1, 2) if donate else ())
        self._decode_exe: dict = {}
        self._prefill_exe: dict = {}
        self.compile_s = 0.0
        if aot:
            self.compile_buckets()

    # ------------------------------------------------------------- build
    @classmethod
    def from_checkpoint(cls, path, config: ErnieMoeConfig, **kw):
        """checkpoint-load → engine: ``path`` is a ``paddle.save``d
        ERNIE-MoE state dict (``ErnieMoeForPretraining`` or bare
        ``ErnieMoeModel`` keys). The warm-start twin of
        ``ServingEngine.from_checkpoint`` — what ``FleetRouter``
        replicas use for ``model_kind="moe"``."""
        from ..framework.io import load as paddle_load
        state = paddle_load(path)
        model = ErnieMoeForPretraining(ErnieMoeModel(config))
        target = model
        if not any(k.startswith("ernie.") for k in state):
            target = model.ernie
        target.set_state_dict(state)
        model.eval()
        return cls(model, config, **kw)

    def compile_buckets(self):
        """AOT-compile every (prefill, decode) bucket program — same
        zero-recompile-at-serving-time contract as ``ServingEngine``."""
        from ..observability.instrument import record_compile
        t0 = time.perf_counter()
        p = self.pool
        sds = jax.ShapeDtypeStruct
        kp = sds(p.k_pages.shape, p.k_pages.dtype)
        params_avals = jax.tree_util.tree_map(
            lambda a: sds(a.shape, a.dtype), self.params)
        key_aval = sds(self._key.shape, self._key.dtype)
        i32 = jnp.int32
        for b in self.decode_buckets:
            if b in self._decode_exe:
                continue
            self._decode_exe[b] = self._decode_jit.lower(
                params_avals, kp, kp, sds((b,), i32), sds((b,), i32),
                sds((b, p.max_pages_per_seq), i32), sds((b,), i32),
                key_aval).compile()
        for sb in self.prefill_buckets:
            if sb in self._prefill_exe:
                continue
            self._prefill_exe[sb] = self._prefill_jit.lower(
                params_avals, kp, kp, sds((1, sb), i32), sds((), i32),
                sds((sb,), i32), key_aval).compile()
        self.compile_s += time.perf_counter() - t0
        record_compile(time.perf_counter() - t0,
                       what="serving_moe_buckets")

    def prefill_signatures(self) -> set:
        return {(1, sb) for sb in self.prefill_buckets}

    def weight_bytes(self) -> int:
        return int(sum(int(getattr(leaf, "nbytes", 0) or 0)
                       for leaf in jax.tree_util.tree_leaves(self.params)))

    def status(self) -> dict:
        return {
            "model": "ernie_moe",
            "num_experts": self.cfg.num_experts,
            "moe_top_k": self.moe_top_k,
            "moe_layers": sum(1 for k in self.kinds if k == "moe"),
            "fused_moe_dispatch": self.use_fused_moe,
            "autofuse": self.autofuse,
            "weights_mb": round(self.weight_bytes() / 2 ** 20, 2),
            "decode_buckets": list(self.decode_buckets),
            "prefill_buckets": list(self.prefill_buckets),
            "max_seq_len": self.max_seq_len,
            "compile_s": round(self.compile_s, 3),
            "aot_programs": len(self._decode_exe) + len(self._prefill_exe),
            "pool": self.pool.stats(),
        }

    # ------------------------------------------------------------- steps
    def _decode_fn(self, bucket):
        if bucket in self._decode_exe:
            return self._decode_exe[bucket]
        if bucket not in self.decode_buckets:
            raise EngineShapeError(
                f"decode batch {bucket} is not an AOT bucket "
                f"{self.decode_buckets}")
        return self._decode_jit

    def _prefill_fn(self, bucket):
        if bucket in self._prefill_exe:
            return self._prefill_exe[bucket]
        if bucket not in self.prefill_buckets:
            raise EngineShapeError(
                f"prefill length {bucket} is not an AOT bucket "
                f"{self.prefill_buckets}")
        return self._prefill_jit

    def prefill(self, seq_id, prompt_ids) -> int:
        prompt = self._check_prompt_room(prompt_ids)
        n = int(prompt.shape[0])
        sb = self.prefill_bucket(n)
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
        self._last_token[seq_id] = tok
        return tok

    def decode(self, seq_ids, bucket=None):
        n = len(seq_ids)
        bucket = self.decode_bucket(n) if bucket is None else bucket
        if n > bucket:
            raise EngineShapeError(f"{n} sequences > bucket {bucket}")
        slots = list(seq_ids) + [None] * (bucket - n)
        lens = self.pool.lens_array(slots)
        table = self.pool.table_array(slots)
        tokens = np.asarray(
            [self._last_token.get(sid, 0) for sid in slots], np.int32)
        positions = np.maximum(lens - 1, 0).astype(np.int32)
        kp, vp, nxt = self._decode_fn(bucket)(
            self.params, self.pool.k_pages, self.pool.v_pages,
            jnp.asarray(tokens), jnp.asarray(positions),
            jnp.asarray(table), jnp.asarray(lens), self._next_key())
        self.pool.bind(kp, vp)
        out = [int(t) for t in np.asarray(nxt)[:n]]
        for sid, t in zip(seq_ids, out):
            self._last_token[sid] = t
        return out

    def release(self, seq_id, token_ids=None):
        self._last_token.pop(seq_id, None)
        self.pool.free(seq_id)
