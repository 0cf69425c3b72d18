"""The plain GLM-5.2 model the serving programs are held to: one
sequence's whole forward pass in ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``, with no cache, kernel or
batching. The equations are :mod:`.glm5`'s module docstring; here they
are written out the straightforward way:

- attention in the **expanded** form (``k_nope`` and ``v`` of every head
  made from ``c_kv`` at every position), scores over all positions with
  a mask that holds each query to its selection;
- the index scores dense ``[S, S]``, the selection ``jax.lax.top_k`` of
  each row (ties to the lower position) among ``s <= t``;
- each expert of the chip's share computed over every token and weighed
  by its gate, which is 0 where the expert was not chosen.

Departures from the published model, here and in the program alike: the
vocabulary may be a slice (the logits are over it); the expert layer
computes the held experts' share (``experts_held`` from
``expert_offset``) and the shared expert, and leaves out what the other
chips' experts add; index keys are kept in the weights' type, not
quantised; multi-token prediction is not served.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .glm5 import layer_norm, rms_norm, rope

__all__ = ["forward", "layer", "selections", "expert_share"]


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def expert_share(p, a, cfg):
    """The routed experts' part of one expert layer over ``a`` ``[S, H]``
    that the experts ``expert_offset ..`` ``+ experts_held`` give, each
    over every token and weighed by its gate (0 where not chosen)."""
    logits = a @ p["router"]
    s = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(s + p["router_bias"], cfg.num_experts_per_tok)
    chosen = jnp.zeros_like(s).at[jnp.arange(a.shape[0])[:, None], idx] \
        .set(1.0)
    g = s * chosen
    if cfg.norm_topk_prob:
        g = g / jnp.sum(g, -1, keepdims=True)
    g = g * cfg.routed_scaling_factor
    out = jnp.zeros_like(a)
    for e in range(cfg.experts_held):
        gu = a @ p["experts_gate_up"][e]
        h = jax.nn.silu(gu[:, :cfg.moe_intermediate_size]) \
            * gu[:, cfg.moe_intermediate_size:]
        out = out + g[:, cfg.expert_offset + e, None] \
            * (h @ p["experts_down"][e])
    return out


def _swiglu(a, w_gate_up, w_down):
    gu = a @ w_gate_up
    F = w_down.shape[0]
    return (jax.nn.silu(gu[:, :F]) * gu[:, F:]) @ w_down


def selections(p, a, c_q, cfg):
    """``S_t`` of a ``full`` layer as a mask ``[S, S]``: the exact top
    ``index_topk`` of the dense index scores among ``s <= t``."""
    S = a.shape[0]
    nI, dI, r = cfg.index_n_heads, cfg.index_head_dim, cfg.index_rope_dim
    pos = jnp.arange(S)
    qi = (c_q @ p["wi_q"]).reshape(S, nI, dI)
    qi = jnp.concatenate([rope(qi[..., :r], pos, cfg.rope_theta),
                          qi[..., r:]], -1)
    ki = layer_norm(a @ p["wi_k"], p["wi_norm_w"], p["wi_norm_b"],
                    cfg.rms_norm_eps)
    ki = jnp.concatenate([rope(ki[:, :r], pos, cfg.rope_theta), ki[:, r:]],
                         -1)
    w = (a @ p["wi_w"]) / jnp.sqrt(nI * 1.0)
    score = jnp.einsum("tj,tjs->ts", w,
                       jax.nn.relu(jnp.einsum("tjd,sd->tjs", qi, ki))) \
        / jnp.sqrt(dI * 1.0)
    causal = pos[None] <= pos[:, None]
    k = min(cfg.index_topk, S)
    _, idx = jax.lax.top_k(jnp.where(causal, score, -jnp.inf), k)
    mask = jnp.zeros((S, S), bool).at[pos[:, None], idx].set(True)
    return mask & causal


def layer(p, x, l, cfg, selected):
    """One layer over ``x`` ``[S, H]``; ``selected`` maps a ``full``
    layer's index to its mask (filled here at a ``full`` layer). Returns
    the new ``x``."""
    eps, nh = cfg.rms_norm_eps, cfg.num_attention_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    S = x.shape[0]
    pos = jnp.arange(S)
    a = rms_norm(x, p["attn_norm"], eps)
    c_q = rms_norm(a @ p["wq_a"], p["q_norm"], eps)
    q = (c_q @ p["wq_b"]).reshape(S, nh, dn + dr)
    q_nope, q_rope = q[..., :dn], rope(q[..., dn:], pos, cfg.rope_theta)
    kv = a @ p["wkv_a"]
    c_kv = rms_norm(kv[:, :cfg.kv_lora_rank], p["kv_norm"], eps)
    k_rope = rope(kv[:, cfg.kv_lora_rank:], pos, cfg.rope_theta)
    kvb = (c_kv @ p["wkv_b"]).reshape(S, nh, dn + dv)
    k_nope, v = kvb[..., :dn], kvb[..., dn:]
    if cfg.indexer_types[l] == "full":
        selected[l] = selections(p, a, c_q, cfg)
    mask = selected[cfg.share_source(l)]
    score = (jnp.einsum("thd,shd->hts", q_nope, k_nope)
             + jnp.einsum("thd,sd->hts", q_rope, k_rope)) \
        / jnp.sqrt(1.0 * (dn + dr))
    prob = jax.nn.softmax(jnp.where(mask[None], score, -jnp.inf), -1)
    o = jnp.einsum("hts,shd->thd", prob, v).reshape(S, nh * dv)
    x = x + o @ p["wo"]
    a = rms_norm(x, p["ffn_norm"], eps)
    if cfg.mlp_layer_types[l] == "dense":
        return x + _swiglu(a, p["w_gate_up"], p["w_down"])
    return x + expert_share(p, a, cfg) \
        + _swiglu(a, p["shared_gate_up"], p["shared_down"])


def forward(params, ids, cfg, return_selections=False):
    """Float32 logits ``[S, V]`` of one sequence ``ids`` (and, with
    ``return_selections``, each ``full`` layer's selection mask ``[S,
    S]`` by layer)."""
    params = _f32(params)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(ids)]
        selected = {}
        for l, p in enumerate(params["layers"]):
            x = layer(p, x, l, cfg, selected)
        logits = rms_norm(x, params["norm"], cfg.rms_norm_eps) \
            @ params["head"]
    return (logits, selected) if return_selections else logits
