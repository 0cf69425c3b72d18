"""Phi-4-mini-flash, plainly: the forward pass of :mod:`.phi4flash`'s
docstring in ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``. No kernel, no cache, no
chunk, no batch: one sequence, **every layer at every position**, the
state-space scan a ``lax.scan`` a position at a time, attention a dense
masked softmax head by head (``q1`` against ``k1``, ``q2`` against ``k2``,
each onto ``[v1 ; v2]``: no packed rows, no zero halves). It takes the
stacked weights of :func:`.phi4flash.init_phi4flash_weights` and the
config, and nothing else of the program.

Departures from the published description: none known in the
mathematics. The config gives the widths, the window, ``mb_per_layer``
and the tie; the layer map, Mamba's sizes, the pairing of heads,
``lam0(l)``, the sub-layer RMSNorm and the biases are as the papers
(arXiv:2507.06607, 2312.00752, 2410.05258) and the published modeling
file have them as known, written down without network access. The window
is ``sliding_window`` keys including the query's own. ``mode`` rounds
every matmul operand as a lower precision would hold it (``"bf16"``,
``"fp8"``): the control of the tests, never a served path. ``window``
overrides the window (the test of the window by one).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

__all__ = ["forward", "layer_weights"]


def _lower(x, mode):
    if mode is None:
        return x
    kind = {"bf16": jnp.bfloat16, "fp8": jnp.float8_e4m3fn}[mode]
    return x.astype(kind).astype(jnp.float32)


def _ln(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def layer_weights(params, cfg):
    """The stacked weights as a list of ``(kind, leaves)`` by layer."""
    take = lambda tree, i: jax.tree.map(
        lambda a: a[i].astype(jnp.float32), tree)
    f32 = lambda tree: jax.tree.map(lambda a: a.astype(jnp.float32), tree)
    out = []
    for i in range(cfg.n_self_pairs):
        out.append(("mamba", take(params["self_pairs"]["mamba"], i)))
        out.append(("window", take(params["self_pairs"]["attn"], i)))
    out.append(("mamba", f32(params["l16"])))
    out.append(("full", f32(params["l17"])))
    for j in range(cfg.n_cross_pairs):
        out.append(("gmu", take(params["cross_pairs"]["gmu"], j)))
        out.append(("cross", take(params["cross_pairs"]["cross"], j)))
    return out


def _mamba(p, a, cfg, lo):
    """``(mixer output, y before the gate)`` over ``a`` ``[S, H]``."""
    S, K, R, N = a.shape[0], cfg.d_conv, cfg.rank, cfg.d_state
    x, z = jnp.split(lo(a) @ lo(p["w_in"]), 2, axis=-1)
    xp = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), x.dtype), x])
    x = jax.nn.silu(sum(xp[k:k + S] * p["conv_w"][k] for k in range(K))
                    + p["conv_b"])
    proj = lo(x) @ lo(p["w_x"])
    dr, B, C = proj[:, :R], proj[:, R:R + N], proj[:, R + N:]
    dt = jax.nn.softplus(lo(dr) @ lo(p["w_dt"]) + p["b_dt"])
    A = -jnp.exp(p["A_log"])                            # [N, Di]

    def step(s, xs):
        x_t, dt_t, b_t, c_t = xs
        s = jnp.exp(dt_t[None] * A) * s + (dt_t * x_t)[None] * b_t[:, None]
        return s, jnp.sum(s * c_t[:, None], 0)

    _, y = jax.lax.scan(step, jnp.zeros_like(A), (x, dt, B, C))
    y = y + p["D"] * x
    return lo(y * jax.nn.silu(z)) @ lo(p["w_out"]), y


def _diff_attention(p, q, k, v, mask, layer, cfg, lo):
    """``q`` ``[S, nh, d]``, ``k``, ``v`` ``[S, nkv, d]`` heads; pair ``i``
    of the queries reads pair ``i // (query pairs / KV pairs)``."""
    S, d = q.shape[0], cfg.head_dim
    r = cfg.q_pairs // cfg.kv_pairs
    lam0 = 0.8 - 0.6 * math.exp(-0.3 * layer)
    lam = jnp.exp(jnp.sum(p["lam"][0] * p["lam"][1])) \
        - jnp.exp(jnp.sum(p["lam"][2] * p["lam"][3])) + lam0
    outs = []
    for i in range(cfg.q_pairs):
        j = i // r
        vj = jnp.concatenate([v[:, 2 * j], v[:, 2 * j + 1]], -1)   # [S, 2d]
        both = []
        for half in (0, 1):
            s = lo(q[:, 2 * i + half]) @ lo(k[:, 2 * j + half]).T \
                / math.sqrt(d)
            pr = jax.nn.softmax(jnp.where(mask, s, -1e30), -1)
            both.append(lo(pr) @ lo(vj))
        o = both[0] - lam * both[1]
        o = o / jnp.sqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                         + cfg.layer_norm_eps) * p["subln"]
        outs.append(o * (1.0 - lam0))
    return lo(jnp.concatenate(outs, -1)) @ lo(p["w_o"]) + p["b_o"]


def forward(params, ids, cfg, mode=None, window=None):
    """Float32 logits ``[S, V]`` of one sequence ``ids`` ``[S]``."""
    lo = lambda x: _lower(x, mode)
    eps, d = cfg.layer_norm_eps, cfg.head_dim
    nh, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
    W = cfg.sliding_window if window is None else window
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(ids, jnp.int32)
        S = ids.shape[0]
        embed = params["embed"].astype(jnp.float32)
        x = embed[ids]
        pos = jnp.arange(S)
        causal = pos[None] <= pos[:, None]
        windowed = causal & (pos[None] > pos[:, None] - W)
        memory = k17 = v17 = None
        for l, (kind, p) in enumerate(layer_weights(params, cfg)):
            a = _ln(x, p["ln1_w"], p["ln1_b"], eps)
            if kind == "mamba":
                mix, y = _mamba(p, a, cfg, lo)
                if l == cfg.memory_layer:
                    memory = y
            elif kind == "gmu":
                mix = lo(jax.nn.silu(lo(a) @ lo(p["w_g1"])) * memory) \
                    @ lo(p["w_g2"])
            else:
                qkv = lo(a) @ lo(p["w_qkv"]) + p["b_qkv"]
                q = qkv[:, :nh * d].reshape(S, nh, d)
                if kind == "cross":
                    k, v = k17, v17
                else:
                    k = qkv[:, nh * d:(nh + nkv) * d].reshape(S, nkv, d)
                    v = qkv[:, (nh + nkv) * d:].reshape(S, nkv, d)
                if kind == "full":
                    k17, v17 = k, v
                mix = _diff_attention(
                    p, q, k, v, windowed if kind == "window" else causal,
                    l, cfg, lo)
            x = x + mix
            a = _ln(x, p["ln2_w"], p["ln2_b"], eps)
            g, u = jnp.split(lo(a) @ lo(p["w1"]), 2, axis=-1)
            x = x + lo(u * jax.nn.silu(g)) @ lo(p["w2"])
        h = _ln(x, params["lnf_w"].astype(jnp.float32),
                params["lnf_b"].astype(jnp.float32), eps)
        return lo(h) @ lo(embed).T
