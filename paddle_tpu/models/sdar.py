"""SDAR-MoE (``model_type`` ``sdar_moe``): a Qwen3-MoE-shaped decoder that
generates by diffusion over blocks of tokens.

Published as ``JetLM/SDAR-30B-A3B-Chat``: 48 layers, hidden 2048, 32
query / 4 KV heads of 128, RoPE theta 1e6, RMSNorm 1e-6, no biases, every
layer a mixture of 128 experts of width 768 with 8 a token
(``norm_topk_prob``), no shared expert, vocabulary 151,936, head not tied.
The layer is written here as pure functions over stacked weights; the
serving programs (:mod:`paddle_tpu.serving.sdar_engine`) call them inside
their layer loop, and :mod:`.sdar_reference` is the plain float32 model
they are tested against.

The equations. Tokens ``x`` at absolute positions ``p``; the block of a
position is ``b(p) = p // block_length``.

- Layer: ``h = h + Attn(RMS1(h))``, ``h = h + MoE(RMS2(h))``; after the
  last layer ``RMS_f``, then the untied head ``[hidden, vocab]``.
  ``RMS(v) = v / sqrt(mean(v^2) + eps) * w``.
- Attention: ``q = W_q a`` as ``num_attention_heads`` heads of
  ``head_dim``, ``k`` and ``v`` as ``num_key_value_heads`` heads, no bias;
  ``q`` and ``k`` each through an RMSNorm over the ``head_dim`` of a head
  with a learned scale, then RoPE (rotate-half pairing, absolute
  positions); query head ``j`` reads KV head ``j // (nh / nkv)``; scores
  ``q.k / sqrt(head_dim)``; **position i sees position j iff b(j) <=
  b(i)**: causal between blocks, everything inside its own block;
  softmax; ``W_o`` back to hidden.
- MoE: ``r = softmax(W_r a)`` over the experts **in float32** (matmul and
  softmax, whatever the weights' type); the ``num_experts_per_tok``
  largest, their weights divided by their sum (``norm_topk_prob``);
  ``sum_e w_e W_down,e (silu(W_gate,e a) * W_up,e a)`` over those. No
  token is ever dropped: there is no capacity.
- Generation (greedy, ``low_confidence_dynamic``): the prompt's whole
  blocks are prefilled under the rule above and their K/V committed;
  prefill yields no token. The next block holds the prompt's remaining
  ``P mod block_length`` tokens and a mask token everywhere else. A
  *denoising pass* runs the block's positions against the committed
  prefix and the block itself; at each still-masked position it takes the
  argmax token and its softmax probability (the confidence) and unmasks
  every masked position whose confidence is over the threshold if those
  are at least ``block_length / steps``, else that many of the highest
  confidence (fewer if fewer are masked). Once no position is masked a
  *commit pass* over the final tokens stores the block's K/V, and the
  block's tokens are the request's next tokens.

**Expert weights are stacked flat** over layers and experts, ``[L * E,
...]``: the expert product is a grouped matmul over the tokens sorted by
expert (:mod:`paddle_tpu.kernels.grouped_matmul`: a Pallas kernel whose
row tile follows the rows an expert gets), and a layer reads its own
experts by handing the kernel its index, which the weight block's index
map adds to the expert's, so no layer's 1.2 GB of experts is ever cut
out of the stack (a ``lax.scan`` over ``[L, E, ...]`` copies each slice
before a kernel may read it). The gate and up projections are one array
``[L * E, hidden, 2 * width]``. ``jax.lax.ragged_dot`` with every other
layer's groups empty is the same product and stays as the reference path
(``use_kernel=False``).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..kernels.grouped_matmul import (group_rows, grouped_matmul,
                                      grouped_matmul_reference)

__all__ = ["SdarMoeConfig", "sdar_moe_tiny_config", "sdar_weight_shapes",
           "init_sdar_weights", "rms_norm", "rope", "route", "moe_ffn",
           "attn_qkv", "attn_out", "final_logits", "confidence",
           "choose_unmask", "active_matmul_params"]


@dataclass(frozen=True)
class SdarMoeConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    moe_intermediate_size: int = 768
    num_experts: int = 128
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    max_position_embeddings: int = 32768
    initializer_range: float = 0.02
    # generation: the config gives none of these (the family's generate.py)
    block_length: int = 4
    denoising_steps: int = 4
    confidence_threshold: float = 0.9
    mask_token_id: int = 151669

    @property
    def group(self):
        return self.num_attention_heads // self.num_key_value_heads

    @property
    def unmask_per_pass(self):
        return max(1, self.block_length // self.denoising_steps)


def sdar_moe_tiny_config(**kw):
    base = dict(vocab_size=512, hidden_size=64, num_hidden_layers=3,
                num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                moe_intermediate_size=32, num_experts=8,
                num_experts_per_tok=2, max_position_embeddings=256,
                mask_token_id=511)
    base.update(kw)
    return SdarMoeConfig(**base)


def sdar_weight_shapes(cfg):
    L, H, V = cfg.num_hidden_layers, cfg.hidden_size, cfg.vocab_size
    nh, nkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    E, F = cfg.num_experts, cfg.moe_intermediate_size
    return {
        "embed": (V, H), "lnf": (H,), "head": (H, V),
        "blocks": {"ln1": (L, H), "wq": (L, H, nh, d), "wk": (L, H, nkv, d),
                   "wv": (L, H, nkv, d), "q_norm": (L, d), "k_norm": (L, d),
                   "wo": (L, nh, d, H), "ln2": (L, H), "router": (L, H, E)},
        "experts": {"gate_up": (L * E, H, 2 * F), "down": (L * E, F, H)},
    }


def init_sdar_weights(cfg, seed, dtype=jnp.float32):
    """Seeded random weights in the stacked layout: matrices N(0,
    ``initializer_range``), norm scales 1 + N(0, range)."""
    shapes = sdar_weight_shapes(cfg)
    flat = [(("blocks", k), s) for k, s in shapes["blocks"].items()] \
        + [(("experts", k), s) for k, s in shapes["experts"].items()] \
        + [((k,), s) for k, s in shapes.items()
           if k not in ("blocks", "experts")]
    key = jax.random.key(int(seed))
    out = {"blocks": {}, "experts": {}}
    for i, (path, shape) in enumerate(sorted(flat)):
        w = cfg.initializer_range * jax.random.normal(
            jax.random.fold_in(key, i), shape, jnp.float32)
        if path[-1] in ("ln1", "ln2", "lnf", "q_norm", "k_norm"):
            w = 1.0 + w
        w = w.astype(dtype)
        if len(path) == 2:
            out[path[0]][path[1]] = w
        else:
            out[path[0]] = w
    return out


# ---------------------------------------------------------------------------
# the layer, piece by piece
# ---------------------------------------------------------------------------

def rms_norm(x, w, eps):
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return xf.astype(x.dtype) * w


def rope(x, positions, theta):
    """Rotate-half RoPE on ``x`` ``[..., heads, d]`` at ``positions``
    ``[...]`` (absolute), angles in float32."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[..., None] * inv
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[..., None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[..., None, :]
    xf = x.astype(jnp.float32)
    half = jnp.concatenate([-xf[..., d // 2:], xf[..., :d // 2]], -1)
    return (xf * cos + half * sin).astype(x.dtype)


def attn_qkv(p, h, positions, cfg):
    """One layer's projections of ``h`` ``[N, H]`` at ``positions``
    ``[N]``: ``q`` ``[N, nh, d]``, ``k`` and ``v`` ``[N, nkv, d]``, q and
    k normed per head and rotated."""
    q = jnp.einsum("nh,hkd->nkd", h, p["wq"])
    k = jnp.einsum("nh,hkd->nkd", h, p["wk"])
    v = jnp.einsum("nh,hkd->nkd", h, p["wv"])
    q = rope(rms_norm(q, p["q_norm"], cfg.rms_norm_eps), positions,
             cfg.rope_theta)
    k = rope(rms_norm(k, p["k_norm"], cfg.rms_norm_eps), positions,
             cfg.rope_theta)
    return q, k, v


def attn_out(p, attn):
    return jnp.einsum("nkd,kdh->nh", attn, p["wo"])


def route(a, w_router, cfg):
    """Softmax over all experts in float32, the top k, renormalised:
    ``(weights [N, k] float32, experts [N, k] int32)``. Nothing is
    dropped and there is no capacity."""
    logits = jnp.dot(a.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, -1)
    w, idx = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    if cfg.norm_topk_prob:
        w = w / jnp.sum(w, -1, keepdims=True)
    return w, idx.astype(jnp.int32)


def moe_ffn(a, layer, w_router, experts, cfg, valid=None, use_kernel=True):
    """The expert layer of ``a`` ``[N, H]``: route, sort the ``N * k``
    assignments by expert, two grouped products over the sorted rows
    (gate and up in one, then down) against the flat ``[L * E, ...]``
    stacks read at ``layer``, weigh and add each token's k results. The
    products are the grouped-matmul kernel, its group metadata made once
    for both; ``use_kernel=False`` takes ``ragged_dot`` (the reference
    path). Returns ``(out [N, H], load [E])``: ``load`` counts the
    assignments of the rows that ``valid`` marks (all of them where it
    is None)."""
    N, k, E = a.shape[0], cfg.num_experts_per_tok, cfg.num_experts
    w, idx = route(a, w_router, cfg)
    flat = idx.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    counts = jnp.zeros((E,), jnp.int32).at[flat].add(1)
    if use_kernel:
        rows = group_rows(counts, N * k)

        def product(x, stack):
            return grouped_matmul(x, stack, rows, layer)
    else:
        def product(x, stack):
            return grouped_matmul_reference(x, stack, counts, layer)
    gate, up = jnp.split(product(a[order // k], experts["gate_up"]), 2,
                         axis=-1)
    y = product((jax.nn.silu(gate) * up).astype(a.dtype), experts["down"])
    y = y.astype(jnp.float32) * w.reshape(-1)[order][:, None]
    out = y[jnp.argsort(order)].reshape(N, k, -1).sum(1).astype(a.dtype)
    if valid is None:
        return out, counts
    load = jnp.zeros((E,), jnp.int32).at[flat].add(
        jnp.repeat(valid.astype(jnp.int32), k))
    return out, load


def final_logits(params, x, cfg):
    """``RMS_f`` and the untied head: float32 logits ``[N, V]``."""
    h = rms_norm(x, params["lnf"], cfg.rms_norm_eps)
    return jnp.dot(h, params["head"], preferred_element_type=jnp.float32)


def confidence(logits):
    """The argmax token of each row and its softmax probability."""
    top = jnp.max(logits, -1)
    lse = jax.nn.logsumexp(logits, -1)
    return jnp.argmax(logits, -1).astype(jnp.int32), jnp.exp(top - lse)


def choose_unmask(conf, masked, threshold, per_pass):
    """``low_confidence_dynamic``: of the masked positions of each block
    (``conf``, ``masked`` ``[B, block]``), every one whose confidence is
    over ``threshold`` if those are at least ``per_pass``, else the
    ``per_pass`` of highest confidence (fewer if fewer are masked)."""
    c = jnp.where(masked, conf, -1.0)
    high = masked & (conf > threshold)
    rank = jnp.argsort(jnp.argsort(-c, axis=-1, stable=True), axis=-1,
                       stable=True)
    top = masked & (rank < per_pass)
    enough = jnp.sum(high, -1, keepdims=True) >= per_pass
    return jnp.where(enough, high, top)


def active_matmul_params(cfg):
    """Parameters one position is multiplied by: attention, the router,
    its k experts, in every layer, and the head."""
    H, d = cfg.hidden_size, cfg.head_dim
    attn = H * d * (2 * cfg.num_attention_heads
                    + 2 * cfg.num_key_value_heads)
    moe = H * cfg.num_experts \
        + cfg.num_experts_per_tok * 3 * H * cfg.moe_intermediate_size
    return cfg.num_hidden_layers * (attn + moe) + H * cfg.vocab_size
