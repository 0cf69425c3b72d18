"""GLM-5.2 (``model_type`` ``glm_moe_dsa``): latent attention (MLA), a
learned sparse selection (DSA) whose choice is shared down the layers
(IndexShare), and 256 sigmoid-routed experts of which 8 are chosen a token
beside one shared expert.

Published as ``zai-org/GLM-5.2``: 78 layers, hidden 6144, 64 heads,
``q_lora_rank`` 2048, ``kv_lora_rank`` 512, a query/key head of 192 + 64
(no position + RoPE), a value head of 256, an indexer of 32 heads of 128
that picks ``index_topk`` 2048 positions, dense layers 12288 wide and
experts 2048 wide, RMSNorm 1e-5, RoPE theta 8e6 on interleaved pairs,
vocabulary 154,880, untied head. The layers are pure functions; the
serving programs (:mod:`paddle_tpu.serving.glm5_engine`) call them, and
:mod:`.glm5_reference` is the plain float32 model they are tested
against.

The equations (``x`` the residual stream, ``RMS`` RMSNorm with a learned
scale, ``LN`` LayerNorm with scale and bias):

- **Block**: ``h = x + Attn(RMS(x))``; ``y = h + FFN(RMS(h))``; after the
  last layer ``RMS`` and the head ``W_head`` (not tied).
- **MLA**: ``c_q = RMS(a W_qa)``; ``[q_nope (192) | q_rope (64)] = c_q
  W_qb`` a head; ``[c_kv (512) | k_rope (64)] = a W_kva``, ``c_kv <-
  RMS(c_kv)``, ``k_rope`` one for all heads; ``[k_nope (192) | v (256)] =
  c_kv W_kvb`` a head. RoPE (theta 8e6, pairs ``(2i, 2i+1)``) on
  ``q_rope`` and ``k_rope``. ``score = (q_nope . k_nope + q_rope .
  k_rope) / sqrt(256)`` over the selected positions ``S_t`` only, ``o =
  softmax(score) v``, the heads side by side through ``W_o``.
- **Absorbed** (the same mathematics reassociated, what the cache
  serves): ``q_lat = q_nope W_kb,h`` (512 a head), ``score = q_lat . c_kv
  + q_rope . k_rope``, ``o_lat = sum p c_kv``, ``o_h = o_lat W_vb,h``. A
  token's cache row is ``[c_kv | k_rope]``, 576 numbers a layer.
- **Indexer** (layers whose ``indexer_types`` entry is ``full``): ``q^I
  = c_q W^I_q`` (32 heads of 128), ``k^I = LN(a W^I_k)`` (one head of
  128), RoPE on the first 64 of the 128 of both, ``w = a W^I_w /
  sqrt(32)``; ``I[t, s] = sum_j w[t, j] relu(q^I[t, j] . k^I[s]) /
  sqrt(128)`` for ``s <= t``; ``S_t`` = the exact top ``index_topk`` of
  ``I[t, .]``, ties to the lower position, and every ``s <= t`` where
  there are fewer.
- **IndexShare**: a ``shared`` layer has no indexer and attends over the
  ``S_t`` of the nearest ``full`` layer below it.
- **MoE** (``mlp_layer_types`` ``sparse``): ``s = sigmoid(a W_r)`` in
  float32 over all routed experts; the choice is the top 8 of ``s + b``
  (``b`` the bias correction, ``noaux_tc``, one group); gates ``g_i = 2.5
  s_i / sum_chosen s``; ``y = sum_{i in chosen and held} g_i E_i(a) +
  E_shared(a)``, ``E(a) = (silu(a W_g) * a W_u) W_d``. Dense layers the
  same ``E`` 12288 wide.

**One chip's share of a deployment.** ``experts_held`` experts from
``expert_offset`` live here: the router scores all ``n_routed_experts``,
the layer computes its own experts' part for the tokens routed to them
and the shared expert, and what absent experts would add is left out, in
the program and the reference alike (the deployment adds it through an
exchange that one chip does not run). The vocabulary may be a slice.
Multi-token prediction is not served.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..kernels.grouped_matmul import (group_rows, grouped_matmul,
                                      grouped_matmul_reference)

__all__ = ["Glm5Config", "glm5_tiny_config", "glm5_layer_shapes",
           "init_glm5_weights", "rms_norm", "layer_norm", "rope",
           "mla_project", "indexer_project", "absorbed_query",
           "latent_row", "mla_output", "exact_topk_mask", "top_positions",
           "route", "swiglu", "held_expert_ffn", "ffn",
           "final_logits"]


@dataclass(frozen=True)
class Glm5Config:
    vocab_size: int = 154880
    hidden_size: int = 6144
    num_attention_heads: int = 64
    q_lora_rank: int = 2048
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_rope_dim: int = 64
    index_topk: int = 2048
    intermediate_size: int = 12288
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 256         # the router's outputs
    experts_held: int = 16              # experts computed here
    expert_offset: int = 0              # the first of them (rank x held)
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-5
    rope_theta: float = 8e6
    max_position_embeddings: int = 1048576
    # the served layers, in order: the published layers 2..6
    mlp_layer_types: tuple = ("dense", "sparse", "sparse", "sparse",
                              "sparse")
    indexer_types: tuple = ("full", "shared", "shared", "shared", "full")
    initializer_range: float = 0.02

    def __post_init__(self):
        if len(self.mlp_layer_types) != len(self.indexer_types):
            raise ValueError("one mlp and one indexer type a layer")
        if self.indexer_types[0] != "full":
            raise ValueError("the first served layer has an indexer: a "
                             "shared layer reads the one below it")
        if not 0 <= self.expert_offset <= self.n_routed_experts \
                - self.experts_held:
            raise ValueError("the held experts lie among the routed ones")

    @property
    def num_hidden_layers(self):
        return len(self.mlp_layer_types)

    @property
    def qk_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self):
        """What a token's cache row holds a layer: ``c_kv`` and
        ``k_rope``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def row_width(self):
        """The cache row as laid out: ``[c_kv | k_rope | 0]``, filled to
        a whole number of 256 numbers, so that packed two bfloat16 to a
        32-bit word it is a whole number of 128 lanes
        (:mod:`paddle_tpu.kernels.sparse_mla`: 576 numbers in 768, 384
        words)."""
        return -(-self.latent_dim // 256) * 256

    @property
    def full_layers(self):
        return tuple(l for l, t in enumerate(self.indexer_types)
                     if t == "full")

    def index_slot(self, layer):
        """The index-key page array of a ``full`` layer."""
        return self.full_layers.index(layer)

    def share_source(self, layer):
        """The ``full`` layer whose selection ``layer`` attends over."""
        return max(l for l in self.full_layers if l <= layer)

    @property
    def moe_layers(self):
        return tuple(l for l, t in enumerate(self.mlp_layer_types)
                     if t == "sparse")


def glm5_tiny_config(**kw):
    """Five layers of every kind at widths a CPU runs: a selection of 16
    over contexts of 64 to 256 positions, 32 routed experts of which 8
    are held."""
    base = dict(vocab_size=160, hidden_size=64, num_attention_heads=4,
                q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=16, index_n_heads=4,
                index_head_dim=16, index_rope_dim=8, index_topk=16,
                intermediate_size=96, moe_intermediate_size=32,
                n_routed_experts=32, experts_held=8, expert_offset=8,
                num_experts_per_tok=4, max_position_embeddings=1024)
    base.update(kw)
    return Glm5Config(**base)


# --------------------------------------------------------------------------
# weights: a list of layers, each a dict of its kind's leaves
# --------------------------------------------------------------------------

def glm5_layer_shapes(cfg, layer):
    """``{leaf: shape}`` of one served layer."""
    H, nh = cfg.hidden_size, cfg.num_attention_heads
    out = {"attn_norm": (H,), "ffn_norm": (H,),
           "wq_a": (H, cfg.q_lora_rank), "q_norm": (cfg.q_lora_rank,),
           "wq_b": (cfg.q_lora_rank, nh * cfg.qk_head_dim),
           "wkv_a": (H, cfg.latent_dim), "kv_norm": (cfg.kv_lora_rank,),
           "wkv_b": (cfg.kv_lora_rank,
                     nh * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
           "wo": (nh * cfg.v_head_dim, H)}
    if cfg.indexer_types[layer] == "full":
        dI = cfg.index_head_dim
        out.update(wi_q=(cfg.q_lora_rank, cfg.index_n_heads * dI),
                   wi_k=(H, dI), wi_norm_w=(dI,), wi_norm_b=(dI,),
                   wi_w=(H, cfg.index_n_heads))
    if cfg.mlp_layer_types[layer] == "dense":
        F = cfg.intermediate_size
        out.update(w_gate_up=(H, 2 * F), w_down=(F, H))
    else:
        E, Fe = cfg.experts_held, cfg.moe_intermediate_size
        out.update(router=(H, cfg.n_routed_experts),
                   router_bias=(cfg.n_routed_experts,),
                   experts_gate_up=(E, H, 2 * Fe), experts_down=(E, Fe, H),
                   shared_gate_up=(H, 2 * Fe), shared_down=(Fe, H))
    return out


NORMS = ("attn_norm", "ffn_norm", "q_norm", "kv_norm", "wi_norm_w", "norm")
FLOAT32_LEAVES = ("router", "router_bias")


# the query and key/value up-projections drawn three times wider: at the
# initializer's spread the scores are all but flat over a selection, and
# which positions were selected would hardly move a layer's output
WIDE = ("wq_b", "wkv_b")


def init_glm5_weights(cfg, seed=0, dtype=jnp.float32):
    """Random weights for the tests: matrices N(0, initializer_range)
    (``WIDE`` ones three times that), norm scales 1 + N(0, range), the
    LayerNorm bias and the router bias N(0, 0.1), the router in float32
    whatever ``dtype``. ``{"embed", "norm", "head", "layers": [...]}``."""
    std = cfg.initializer_range
    key = jax.random.key(seed)

    def leaf(name, shape, k):
        noise = jax.random.normal(k, shape, jnp.float32)
        if name in NORMS:
            w = 1.0 + std * noise
        elif name in ("wi_norm_b", "router_bias"):
            w = 0.1 * noise
        elif name in WIDE:
            w = 3 * std * noise
        else:
            w = std * noise
        return w if name in FLOAT32_LEAVES else w.astype(dtype)

    H, V = cfg.hidden_size, cfg.vocab_size
    ks = jax.random.split(key, 3 + cfg.num_hidden_layers)
    layers = []
    for l in range(cfg.num_hidden_layers):
        shapes = sorted(glm5_layer_shapes(cfg, l).items())
        lk = jax.random.split(ks[3 + l], len(shapes))
        layers.append({n: leaf(n, s, k) for (n, s), k in zip(shapes, lk)})
    return {"embed": leaf("embed", (V, H), ks[0]),
            "norm": leaf("norm", (H,), ks[1]),
            "head": leaf("head", (H, V), ks[2]), "layers": layers}


# --------------------------------------------------------------------------
# the layer, piece by piece
# --------------------------------------------------------------------------

def rms_norm(x, w, eps):
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (xf * w.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x, w, b, eps):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), -1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)
            + b.astype(jnp.float32)).astype(x.dtype)


def rope(x, positions, theta):
    """RoPE on interleaved pairs ``(2i, 2i+1)`` of the last axis of ``x``
    ``[N, ..., d]`` at ``positions`` ``[N]``, angles in float32."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32).reshape(
        (-1,) + (1,) * (x.ndim - 2) + (1,)) * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    a, b = xf[..., 0], xf[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], -1)
    return out.reshape(x.shape).astype(x.dtype)


def mla_project(p, a, positions, cfg):
    """The attention's projections of ``a`` ``[N, H]`` (the normed
    stream) at ``positions``: ``(c_q [N, q_lora], q_nope [N, nh, 192],
    q_rope [N, nh, 64], c_kv [N, 512], k_rope [N, 64])``, both ropes
    rotated, ``c_q`` and ``c_kv`` normed."""
    eps, nh = cfg.rms_norm_eps, cfg.num_attention_heads
    c_q = rms_norm(a @ p["wq_a"], p["q_norm"], eps)
    q = (c_q @ p["wq_b"]).reshape(-1, nh, cfg.qk_head_dim)
    q_nope = q[..., :cfg.qk_nope_head_dim]
    q_rope = rope(q[..., cfg.qk_nope_head_dim:], positions, cfg.rope_theta)
    kv = a @ p["wkv_a"]
    c_kv = rms_norm(kv[:, :cfg.kv_lora_rank], p["kv_norm"], eps)
    k_rope = rope(kv[:, cfg.kv_lora_rank:], positions, cfg.rope_theta)
    return c_q, q_nope, q_rope, c_kv, k_rope


def indexer_project(p, a, c_q, positions, cfg):
    """The indexer's ``(q^I [N, nI, dI], k^I [N, dI], w [N, nI] float32)``
    with RoPE on the first ``index_rope_dim`` of each head and ``w`` carrying
    both scales (``1 / sqrt(nI)`` and ``1 / sqrt(dI)``)."""
    nI, dI, r = cfg.index_n_heads, cfg.index_head_dim, cfg.index_rope_dim
    qi = (c_q @ p["wi_q"]).reshape(-1, nI, dI)
    qi = jnp.concatenate(
        [rope(qi[..., :r], positions, cfg.rope_theta), qi[..., r:]], -1)
    ki = layer_norm(a @ p["wi_k"], p["wi_norm_w"], p["wi_norm_b"],
                    cfg.rms_norm_eps)
    ki = jnp.concatenate(
        [rope(ki[:, None, :r], positions, cfg.rope_theta)[:, 0],
         ki[:, r:]], -1)
    w = jnp.dot(a, p["wi_w"], preferred_element_type=jnp.float32) \
        * (nI ** -0.5) * (dI ** -0.5)
    return qi, ki, w


def _kv_b(p, cfg):
    w = p["wkv_b"].reshape(cfg.kv_lora_rank, cfg.num_attention_heads, -1)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def absorbed_query(p, q_nope, q_rope, cfg):
    """``[q_nope W_kb,h | q_rope | 0]`` ``[N, nh, row_width]``: one row a
    head against the cache rows ``[c_kv | k_rope | 0]``, so that a single
    product gives ``q_lat . c_kv + q_rope . k_rope``."""
    w_kb, _ = _kv_b(p, cfg)
    q_lat = jnp.einsum("nhd,chd->nhc", q_nope, w_kb,
                       preferred_element_type=jnp.float32)
    fill = cfg.row_width - cfg.latent_dim
    parts = [q_lat.astype(q_rope.dtype), q_rope]
    if fill:
        parts.append(jnp.zeros(q_rope.shape[:2] + (fill,), q_rope.dtype))
    return jnp.concatenate(parts, -1)


def latent_row(c_kv, k_rope, cfg):
    """A token's cache row ``[c_kv | k_rope | 0]`` ``[N, row_width]``."""
    fill = cfg.row_width - cfg.latent_dim
    parts = [c_kv, k_rope.astype(c_kv.dtype)]
    if fill:
        parts.append(jnp.zeros((c_kv.shape[0], fill), c_kv.dtype))
    return jnp.concatenate(parts, -1)


def mla_output(p, o_lat, cfg, dtype):
    """``o_lat`` ``[N, nh, 512]`` (float32) through ``W_vb,h`` and
    ``W_o``: ``[N, H]``."""
    _, w_vb = _kv_b(p, cfg)
    o = jnp.einsum("nhc,chd->nhd", o_lat.astype(dtype), w_vb,
                   preferred_element_type=jnp.float32).astype(dtype)
    return o.reshape(o.shape[0], -1) @ p["wo"]


# --------------------------------------------------------------------------
# the exact selection
# --------------------------------------------------------------------------

def _order_key(scores):
    """float32 -> uint32 whose unsigned order is the floats' order."""
    bits = jax.lax.bitcast_convert_type(scores.astype(jnp.float32),
                                        jnp.uint32)
    neg = (bits >> 31) == 1
    return jnp.where(neg, ~bits, bits | jnp.uint32(0x80000000))


def exact_topk_mask(scores, valid, k):
    """The ``k`` largest of each row of ``scores`` ``[R, W]`` among the
    ``valid`` positions, ties to the lower position (what
    ``jax.lax.top_k`` keeps), every valid one where there are fewer:
    a bool mask ``[R, W]``. The k-th largest value is found bit by bit
    (32 passes of a count over the row), so nothing is sorted."""
    u = jnp.where(valid, _order_key(scores), jnp.uint32(0))
    R = scores.shape[0]

    def bit(i, tau):
        cand = tau | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        ok = jnp.sum((u >= cand[:, None]).astype(jnp.int32), 1) >= k
        return jnp.where(ok, cand, tau)

    tau = jax.lax.fori_loop(0, 32, bit, jnp.zeros((R,), jnp.uint32))
    above = valid & (u > tau[:, None])
    tie = valid & (u == tau[:, None])
    need = k - jnp.sum(above.astype(jnp.int32), 1, keepdims=True)
    n_tie = jnp.sum(tie.astype(jnp.int32), 1, keepdims=True)
    # a cumulative sum only where some row must cut its ties
    tied = jax.lax.cond(
        jnp.any(n_tie > need),
        lambda: tie & (jnp.cumsum(tie.astype(jnp.int32), 1) <= need),
        lambda: tie)
    return above | tied


def top_positions(scores, valid, k):
    """``S_t`` of every row as positions: ``jax.lax.top_k`` of ``scores``
    ``[R, W]`` with the positions that are not ``valid`` at ``-inf``
    (ties to the lower position, as :func:`exact_topk_mask` keeps them):
    ``(positions [R, k], ok [R, k])``, ``ok`` False past a row's valid
    count. At the 16 rows of a decode tick this takes 1.6 ms on a v5e,
    where the mask and a compaction of its positions took 6.2."""
    val, idx = jax.lax.top_k(jnp.where(valid, scores, -jnp.inf), k)
    return idx.astype(jnp.int32), val > -jnp.inf


# --------------------------------------------------------------------------
# the feed-forward layers
# --------------------------------------------------------------------------

def swiglu(a, w_gate_up, w_down):
    g, u = jnp.split(a @ w_gate_up, 2, axis=-1)
    return (jax.nn.silu(g) * u).astype(a.dtype) @ w_down


def route(a, w_router, bias, cfg):
    """Sigmoid scores over every routed expert in float32, the choice by
    ``s + b``, gates ``scale * s / sum_chosen s``: ``(gates [N, k]
    float32, experts [N, k] int32)``."""
    logits = jnp.dot(a.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(s + bias.astype(jnp.float32),
                           cfg.num_experts_per_tok)
    g = jnp.take_along_axis(s, idx, 1)
    if cfg.norm_topk_prob:
        g = g / jnp.sum(g, -1, keepdims=True)
    return g * cfg.routed_scaling_factor, idx.astype(jnp.int32)


def held_expert_ffn(p, a, cfg, valid=None, use_kernel=True):
    """The routed part of an expert layer that this chip computes: the
    router over every expert, then the held experts' products for the
    tokens routed to them (rows of ``valid`` tokens only), by the grouped
    matmul over the assignments sorted by expert; an assignment to an
    absent expert is sorted past the held groups and never computed.
    Returns ``(out [N, H], counts [held] int32, (gates, experts))``, the
    last :func:`route`'s."""
    N, k, G = a.shape[0], cfg.num_experts_per_tok, cfg.experts_held
    gates, idx = route(a, p["router"], p["router_bias"], cfg)
    local = idx - cfg.expert_offset
    held = (local >= 0) & (local < G)
    if valid is not None:
        held &= valid[:, None]
    key = jnp.where(held, local, G).reshape(-1)
    order = jnp.argsort(key, stable=True)
    counts = jnp.zeros((G + 1,), jnp.int32).at[key].add(1)[:G]
    if use_kernel:
        rows = group_rows(counts, N * k)

        def product(x, stack):
            return grouped_matmul(x, stack, rows)
    else:
        def product(x, stack):
            return grouped_matmul_reference(x, stack, counts)
    gate, up = jnp.split(product(a[order // k], p["experts_gate_up"]), 2,
                         axis=-1)
    y = product((jax.nn.silu(gate) * up).astype(a.dtype), p["experts_down"])
    mine = held.reshape(-1)[order]
    w = gates.reshape(-1)[order]
    # rows past the held groups come out of the kernel undefined
    y = jnp.where(mine[:, None], y.astype(jnp.float32) * w[:, None], 0.0)
    out = y[jnp.argsort(order)].reshape(N, k, -1).sum(1)
    return out.astype(a.dtype), counts, (gates, idx)


def ffn(p, a, layer, cfg, valid=None, use_kernel=True):
    """The layer's feed-forward part of the normed ``a``: ``(out,
    counts, (gates, experts))``, the last two None in a dense layer."""
    if cfg.mlp_layer_types[layer] == "dense":
        return swiglu(a, p["w_gate_up"], p["w_down"]), None, None
    routed, counts, routes = held_expert_ffn(p, a, cfg, valid, use_kernel)
    return routed + swiglu(a, p["shared_gate_up"], p["shared_down"]), \
        counts, routes


def final_logits(params, x, cfg):
    h = rms_norm(x, params["norm"], cfg.rms_norm_eps)
    return jnp.dot(h, params["head"], preferred_element_type=jnp.float32)


def param_count(cfg):
    """Parameters of the served stack, tables included."""
    n = sum(math.prod(s) for l in range(cfg.num_hidden_layers)
            for s in glm5_layer_shapes(cfg, l).values())
    return n + 2 * cfg.vocab_size * cfg.hidden_size + cfg.hidden_size
