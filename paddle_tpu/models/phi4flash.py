"""Phi-4-mini-flash (``model_type`` ``phi4flash``, the "SambaY" decoder of
arXiv:2507.06607): a state-space / window-attention self-decoder, one
full-attention layer whose K/V is the model's only growing cache, and a
cross-decoder of gated memory units and cross-attention layers that own
no K/V.

Published as ``microsoft/Phi-4-mini-flash-reasoning``: 32 layers, hidden
2560, 40 query / 20 KV heads of 64, ``intermediate_size`` 10240
(SiLU-gated), LayerNorm 1e-5, ``sliding_window`` 512, ``mb_per_layer`` 2,
vocabulary 200,064 with a tied head, 262,144 positions, no rotary or
learned position. The layers are pure functions over weights stacked by
kind; the serving programs (:mod:`paddle_tpu.serving.phi4flash_engine`)
call them, and :mod:`.phi4flash_reference` is the plain float32 model they
are tested against.

The equations. ``d`` = hidden, layers ``l`` = 0..L-1, ``LN`` = LayerNorm
with scale and bias.

- **Every layer**: ``h = h + Mix_l(LN1_l(h))``, then ``h = h +
  MLP_l(LN2_l(h))``. ``MLP(a)``: ``[g, u] = a W_1`` (no bias, gate
  first), ``(u * silu(g)) W_2`` (no bias). After the last layer a final
  ``LN``, then logits ``= h E^T`` with the embedding ``E`` (tied, no
  bias). Token embedding only: no position is added anywhere.
- **Which mixer** (``L % 4 == 0``, ``M = L / 2``): Mamba at even ``l <=
  M``; attention under the window at odd ``l < M``; full causal attention
  at ``l = M + 1``, whose ``k``, ``v`` are *the* cache; gated memory units
  at even ``l >= M + 2``; cross-attention onto layer ``M + 1``'s ``k``,
  ``v`` at odd ``l >= M + 3``. The memory is layer ``M``'s. At 32 layers:
  Mamba 0, 2, .., 16; window 1, 3, .., 15; full 17; memory units 18, ..,
  30; cross 19, .., 31.
- **Mamba** (``Di = expand * d``, ``N = d_state``, kernel ``d_conv``,
  ``R = dt_rank``): ``[x, z] = a W_in`` (no bias); ``x = silu(conv(x))``,
  a causal depthwise convolution over the last ``d_conv`` positions with
  bias; ``[dr, B, C] = x W_x`` (no bias); ``dt = softplus(dr W_dt +
  b_dt)``; ``A = -exp(A_log)``; state ``s_t = exp(dt_t * A) * s_{t-1} +
  (dt_t * x_t) (x) B_t``, ``s_{-1} = 0``; ``y_t = s_t C_t + D * x_t``;
  output ``(y * silu(z)) W_out`` (no bias). **Layer M also keeps ``m_t =
  y_t``, the scan's output before the gate, as the memory of this
  position.** State, ``dt`` and the recurrence in float32.
- **Gated memory unit**: ``(silu(a W_1) * m) W_2``, no bias, ``m`` layer
  M's memory *at the same position*. It has no cache.
- **Differential attention** (window and full layers with their own ``q,
  k, v = a W_qkv + b``; cross layers with ``q = a W_q + b`` only and the
  full layer's ``k``, ``v``). Query pair ``i`` = heads ``(2i, 2i+1)`` =
  ``(q1_i, q2_i)``; KV pair ``j`` = heads ``(2j, 2j+1)`` = ``(k1_j,
  k2_j)``, ``(v1_j, v2_j)``; pair ``i`` reads pair ``j = i // (query pairs
  / KV pairs)``; ``V_j = [v1_j ; v2_j]`` (twice a head wide). ``A1_i =
  softmax(q1_i k1_j^T / sqrt(head)) V_j``, ``A2_i`` likewise from ``q2_i,
  k2_j``, under the layer's mask; ``lam = exp(lq1 . lk1) - exp(lq2 . lk2)
  + lam0(l)`` with four learned head-wide vectors a layer and ``lam0(l) =
  0.8 - 0.6 exp(-0.3 l)``; ``o_i = RMSNorm(A1_i - lam A2_i) * (1 -
  lam0(l))`` with a learned scale; the ``o_i`` side by side through
  ``W_o`` with bias. Masks: a window layer's position ``t`` sees ``t -
  (window - 1) <= j <= t``; the full and cross layers ``j <= t``. Softmax
  in float32.
- **Prefill yields a token from the last position only**, so the
  cross-decoder (layers ``M + 2`` on), the final norm and the head run at
  a prompt's last position alone; the self-decoder (layers 0..M+1) runs
  at every position: it writes the state, the window rows and the pages.

**A pair of heads is one head of the cache.** Heads ``(2j, 2j+1)`` lie side
by side in ``W_qkv``'s output, so ``[k1_j ; k2_j]`` and ``[v1_j ; v2_j]``
are contiguous, twice a head wide: a token's cache row is
``num_key_value_heads / 2`` such heads of ``2 * head_dim`` side by side
(``kv_pairs * pair_dim`` wide: the pool and the window rows are pools of
rows, :func:`paddle_tpu.kernels.paged_attention.paged_attention_decode_rows`).
A query pair becomes two rows of a pair's width, ``[q1 ; 0]`` and ``[0 ;
q2]`` (:func:`paired_queries`): the zero half takes the other key out of
the score, and a paged kernel that takes q, k and v rows of one width
gives ``A1`` and ``A2`` with K and V read once a layer.

What the config does not give (the layer map, Mamba's sizes, the
pairing, ``lam0``, the sub-layer norm, which projections carry a bias) is
as the papers and the published modeling file have it as known, and the
benchmark's configuration file lists each under ``assumed``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..kernels.selective_scan import (selective_scan_chunk,
                                      selective_scan_reference)

__all__ = ["Phi4FlashConfig", "phi4flash_tiny_config",
           "phi4flash_weight_shapes", "init_phi4flash_weights",
           "layer_kinds", "layer_norm", "mlp", "mamba_chunk", "mamba_step",
           "memory_unit", "attn_project", "paired_queries", "diff_combine",
           "unpair_outputs", "diff_attention_dense", "ring_positions",
           "window_chunk_attention", "final_logits", "forward_full"]


@dataclass(frozen=True)
class Phi4FlashConfig:
    vocab_size: int = 200064
    hidden_size: int = 2560
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    intermediate_size: int = 10240
    sliding_window: int = 512
    mb_per_layer: int = 2
    layer_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    initializer_range: float = 0.02
    # Mamba's defaults (arXiv:2312.00752): the config gives none of them
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 0            # 0: ceil(hidden / 16)

    def __post_init__(self):
        if self.num_hidden_layers % 4 or self.num_hidden_layers < 8:
            raise ValueError("num_hidden_layers: a multiple of 4, at least "
                             "8 (at 4 there is no memory unit)")
        if self.num_attention_heads % 2 or self.num_key_value_heads % 2 \
                or (self.num_attention_heads // 2) \
                % (self.num_key_value_heads // 2):
            raise ValueError("heads pair up, and KV pairs divide the "
                             "query pairs")

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self):
        return self.expand * self.hidden_size

    @property
    def rank(self):
        return self.dt_rank or math.ceil(self.hidden_size / 16)

    @property
    def q_pairs(self):
        return self.num_attention_heads // 2

    @property
    def kv_pairs(self):
        return self.num_key_value_heads // 2

    @property
    def pair_dim(self):
        """Width of a cache row: a pair of heads side by side."""
        return 2 * self.head_dim

    @property
    def n_self_pairs(self):
        """(Mamba, window attention) pairs before the memory layer."""
        return self.num_hidden_layers // 4

    @property
    def n_cross_pairs(self):
        """(memory unit, cross attention) pairs after the full layer."""
        return self.num_hidden_layers // 4 - 1

    @property
    def n_mamba(self):
        return self.n_self_pairs + 1

    @property
    def memory_layer(self):
        return self.num_hidden_layers // 2

    @property
    def full_layer(self):
        return self.num_hidden_layers // 2 + 1


def phi4flash_tiny_config(**kw):
    """12 layers: three Mamba/window pairs, the memory layer, the full
    layer, two memory-unit/cross pairs."""
    base = dict(vocab_size=128, hidden_size=64, num_hidden_layers=12,
                num_attention_heads=4, num_key_value_heads=2,
                intermediate_size=96, sliding_window=8, d_state=4,
                max_position_embeddings=256)
    base.update(kw)
    return Phi4FlashConfig(**base)


def layer_kinds(cfg):
    """The mixer of every layer: ``mamba``, ``window``, ``full``, ``gmu``
    or ``cross``."""
    M = cfg.memory_layer
    return ["mamba" if l % 2 == 0 and l <= M else
            "window" if l < M else
            "full" if l == M + 1 else
            "gmu" if l % 2 == 0 else "cross"
            for l in range(cfg.num_hidden_layers)]


def lam0(layer):
    """``0.8 - 0.6 exp(-0.3 l)`` (``layer`` may be traced)."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, jnp.float32))


# --------------------------------------------------------------------------
# weights, stacked by kind
# --------------------------------------------------------------------------

def _mlp_shapes(cfg):
    H, F = cfg.hidden_size, cfg.intermediate_size
    return {"ln1_w": (H,), "ln1_b": (H,), "ln2_w": (H,), "ln2_b": (H,),
            "w1": (H, 2 * F), "w2": (F, H)}


def _mamba_shapes(cfg):
    H, Di, N, R = cfg.hidden_size, cfg.d_inner, cfg.d_state, cfg.rank
    return dict(_mlp_shapes(cfg), w_in=(H, 2 * Di),
                conv_w=(cfg.d_conv, Di), conv_b=(Di,),
                w_x=(Di, R + 2 * N), w_dt=(R, Di), b_dt=(Di,),
                A_log=(N, Di), D=(Di,), w_out=(Di, H))


def _attn_shapes(cfg, own_kv=True):
    H, d = cfg.hidden_size, cfg.head_dim
    nq = cfg.num_attention_heads * d
    n = nq + (2 * cfg.num_key_value_heads * d if own_kv else 0)
    return dict(_mlp_shapes(cfg), w_qkv=(H, n), b_qkv=(n,), w_o=(nq, H),
                b_o=(H,), lam=(4, d), subln=(2 * d,))


def _gmu_shapes(cfg):
    H, Di = cfg.hidden_size, cfg.d_inner
    return dict(_mlp_shapes(cfg), w_g1=(H, Di), w_g2=(Di, H))


def phi4flash_weight_shapes(cfg):
    """The stacked layout: ``self_pairs`` (``mamba`` and ``attn`` of
    layers ``2i``, ``2i+1``), ``l16`` (the memory layer), ``l17`` (the
    full layer), ``cross_pairs`` (``gmu`` and ``cross`` of layers ``M + 2
    + 2j``, ``M + 3 + 2j``) and the tables. The state matrix is stored as
    ``A_log [N, Di]`` (states major, channels on the lanes: the layout of
    the state itself); the cross layers' ``w_qkv`` holds the query only."""
    stack = lambda n, shapes: {k: (n,) + s for k, s in shapes.items()}
    P, Q = cfg.n_self_pairs, cfg.n_cross_pairs
    return {
        "embed": (cfg.vocab_size, cfg.hidden_size),
        "lnf_w": (cfg.hidden_size,), "lnf_b": (cfg.hidden_size,),
        "self_pairs": {"mamba": stack(P, _mamba_shapes(cfg)),
                       "attn": stack(P, _attn_shapes(cfg))},
        "l16": _mamba_shapes(cfg), "l17": _attn_shapes(cfg),
        "cross_pairs": {"gmu": stack(Q, _gmu_shapes(cfg)),
                        "cross": stack(Q, _attn_shapes(cfg, False))},
    }


_ONES = ("ln1_w", "ln2_w", "lnf_w", "subln")


def draw_leaf(key, name, shape, std, dtype=jnp.float32):
    """One leaf (``shape`` without any stacking axis is not needed: the
    draw is elementwise). Matrices N(0, std); norm scales 1 + N(0, std);
    ``lam`` N(0, 0.1); and Mamba's published initialisation where N(0,
    std) would make a state that forgets in two positions or a scan
    whose input is nothing: ``A_log = log(1..N)`` a channel plus noise,
    ``b_dt`` the inverse softplus of a log-uniform step in [1e-3, 1e-1],
    ``D = 1`` plus noise, the convolution U(+-1/sqrt(d_conv)) (under
    N(0, 0.02) the scan's input is 0.02 and the state 1e-6 of ``y``)."""
    noise = std * jax.random.normal(key, shape, jnp.float32)
    if name in _ONES or name == "D":
        w = 1.0 + noise
    elif name == "lam":
        w = noise * (0.1 / std)
    elif name == "conv_w":          # [..., K, Di]: U(+-1/sqrt(K))
        w = jax.random.uniform(key, shape, jnp.float32, -1.0, 1.0) \
            / math.sqrt(shape[-2])
    elif name == "A_log":           # [..., N, Di]
        n = jnp.arange(1, shape[-2] + 1, dtype=jnp.float32)[:, None]
        w = jnp.log(n) + noise
    elif name == "b_dt":
        u = jax.random.uniform(jax.random.fold_in(key, 1), shape)
        dt = jnp.exp(u * (math.log(1e-1) - math.log(1e-3))
                     + math.log(1e-3))
        w = dt + jnp.log(-jnp.expm1(-dt))     # inverse softplus
    else:
        w = noise
    return w.astype(dtype)


def init_phi4flash_weights(cfg, seed=0, dtype=jnp.float32):
    """Every weight in the stacked layout, from the seed."""
    shapes = phi4flash_weight_shapes(cfg)
    leaves, tree = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    key = jax.random.key(seed)
    out = [draw_leaf(jax.random.fold_in(key, i), path[-1].key, shape,
                     cfg.initializer_range, dtype)
           for i, (path, shape) in enumerate(leaves)]
    return jax.tree_util.tree_unflatten(tree, out)


# --------------------------------------------------------------------------
# the pieces
# --------------------------------------------------------------------------

def layer_norm(x, w, b, eps):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), -1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * w + b).astype(x.dtype)


def mlp(p, x, cfg):
    """``x + MLP(LN2(x))`` over ``x`` ``[..., H]``."""
    a = layer_norm(x, p["ln2_w"], p["ln2_b"], cfg.layer_norm_eps)
    g, u = jnp.split(a @ p["w1"], 2, axis=-1)
    return x + (u * jax.nn.silu(g)) @ p["w2"]


def _ssm_inputs(p, xc, cfg):
    """From the convolved ``xc`` ``[..., Di]``: ``dt`` (float32), ``B``,
    ``C`` ``[..., N]`` (float32)."""
    R, N = cfg.rank, cfg.d_state
    proj = (xc @ p["w_x"]).astype(jnp.float32)
    dr, B, C = proj[..., :R], proj[..., R:R + N], proj[..., R + N:]
    dt = jax.nn.softplus(dr @ p["w_dt"].astype(jnp.float32)
                         + p["b_dt"].astype(jnp.float32))
    return dt, B, C


def mamba_chunk(p, x, s, tail, chunk_len, cfg, use_kernel=True):
    """The Mamba mixer over a chunk of one sequence. ``x`` ``[C, H]`` the
    layer's input (before LN1), ``s`` ``[N, Di]`` float32 and ``tail``
    ``[d_conv - 1, Di]`` the state as of the position before the chunk;
    positions at or past ``chunk_len`` are padding and leave the state as
    of the last real one (their ``dt`` is 0). Returns ``(x + mixer, s,
    tail, m)``, ``m`` ``[C, Di]`` the scan's output before the gate."""
    C, K = x.shape[0], cfg.d_conv
    a = layer_norm(x, p["ln1_w"], p["ln1_b"], cfg.layer_norm_eps)
    xi, z = jnp.split(a @ p["w_in"], 2, axis=-1)
    xt = jnp.concatenate([tail.astype(xi.dtype), xi], 0)   # [K-1+C, Di]
    xc = sum(xt[k:k + C] * p["conv_w"][k] for k in range(K)) + p["conv_b"]
    xc = jax.nn.silu(xc)
    dt, B, Cm = _ssm_inputs(p, xc, cfg)
    valid = jnp.arange(C, dtype=jnp.int32) < chunk_len
    dt = jnp.where(valid[:, None], dt, 0.0)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    scan = selective_scan_chunk if use_kernel else selective_scan_reference
    y, s = scan(xc, dt, A, B, Cm, s)
    y = y + p["D"].astype(jnp.float32) * xc.astype(jnp.float32)
    new_tail = jax.lax.dynamic_slice_in_dim(xt, chunk_len, K - 1, 0)
    out = (y.astype(x.dtype) * jax.nn.silu(z)) @ p["w_out"]
    return x + out, s, new_tail.astype(tail.dtype), y.astype(x.dtype)


def mamba_step(p, x, s, tail, cfg):
    """One position of ``B`` sequences: ``x`` ``[B, H]``, ``s`` ``[B, N,
    Di]`` float32, ``tail`` ``[B, d_conv - 1, Di]``. Returns ``(x + mixer,
    s, tail, m)``."""
    a = layer_norm(x, p["ln1_w"], p["ln1_b"], cfg.layer_norm_eps)
    xi, z = jnp.split(a @ p["w_in"], 2, axis=-1)
    xt = jnp.concatenate([tail.astype(xi.dtype), xi[:, None]], 1)
    xc = jax.nn.silu(jnp.einsum("bkc,kc->bc", xt, p["conv_w"])
                     + p["conv_b"])
    dt, B, Cm = _ssm_inputs(p, xc, cfg)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    xf = xc.astype(jnp.float32)
    s = jnp.exp(dt[:, None, :] * A) * s \
        + (dt * xf)[:, None, :] * B[:, :, None]
    y = jnp.einsum("bnc,bn->bc", s, Cm) + p["D"].astype(jnp.float32) * xf
    out = (y.astype(x.dtype) * jax.nn.silu(z)) @ p["w_out"]
    return x + out, s, xt[:, 1:].astype(tail.dtype), y.astype(x.dtype)


def memory_unit(p, x, m, cfg):
    """``x + (silu(LN1(x) W_1) * m) W_2`` with ``m`` the memory layer's
    scan output at the same positions."""
    a = layer_norm(x, p["ln1_w"], p["ln1_b"], cfg.layer_norm_eps)
    return x + (jax.nn.silu(a @ p["w_g1"]) * m) @ p["w_g2"]


def attn_project(p, x, cfg, own_kv=True):
    """``LN1`` and the projection: ``q`` ``[..., q_pairs, 2 * head]`` and,
    where the layer has its own, ``k``, ``v`` ``[..., kv_pairs, 2 *
    head]``: pairs of heads side by side, as the cache holds them."""
    a = layer_norm(x, p["ln1_w"], p["ln1_b"], cfg.layer_norm_eps)
    qkv = a @ p["w_qkv"] + p["b_qkv"]
    lead, w = qkv.shape[:-1], cfg.pair_dim
    nq = cfg.q_pairs * w
    q = qkv[..., :nq].reshape(*lead, cfg.q_pairs, w)
    if not own_kv:
        return q, None, None
    nk = cfg.kv_pairs * w
    k = qkv[..., nq:nq + nk].reshape(*lead, cfg.kv_pairs, w)
    v = qkv[..., nq + nk:].reshape(*lead, cfg.kv_pairs, w)
    return q, k, v


# a KV pair's query rows are filled to this many for the paged decode
# kernel, whose body (the MXU) takes whole bf16 sublane tiles of 16
_GROUP_ROWS = 16


def paired_queries(q, cfg):
    """``q`` ``[n, q_pairs, 2 * head]`` as the rows a paged kernel takes
    against cache rows a pair of heads wide: ``[q1 ; 0]`` and ``[0 ; q2]``
    of every pair, grouped by the KV pair they read, each group filled
    with zero rows to a multiple of 16: ``[n, kv_pairs, rows, 2 *
    head]``."""
    n, d = q.shape[0], cfg.head_dim
    lo = jnp.arange(2 * d) < d
    rows = jnp.stack([jnp.where(lo, q, 0), jnp.where(lo, 0, q)], 2)
    rows = rows.reshape(n, cfg.kv_pairs, -1, 2 * d)
    fill = -rows.shape[2] % _GROUP_ROWS
    if fill:
        rows = jnp.pad(rows, [(0, 0), (0, 0), (0, fill), (0, 0)])
    return rows


def unpair_outputs(out, cfg):
    """What a paged kernel returned for :func:`paired_queries` rows, as
    ``(A1, A2)`` ``[n, q_pairs, 2 * head]``."""
    n, g = out.shape[0], 2 * (cfg.q_pairs // cfg.kv_pairs)
    out = out[:, :, :g].reshape(n, cfg.q_pairs, 2, out.shape[-1])
    return out[:, :, 0], out[:, :, 1]


def diff_combine(p, a1, a2, layer, cfg):
    """``W_o`` of ``RMSNorm(A1 - lam A2) * (1 - lam0)``; ``a1``, ``a2``
    ``[..., q_pairs, 2 * head]`` (float32 from the attention that made
    them; the difference is rounded to the weights' type once, after the
    norm)."""
    lam = p["lam"].astype(jnp.float32)
    l0 = lam0(layer)
    full = jnp.exp(jnp.sum(lam[0] * lam[1])) \
        - jnp.exp(jnp.sum(lam[2] * lam[3])) + l0
    o = a1.astype(jnp.float32) - full * a2.astype(jnp.float32)
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                          + cfg.layer_norm_eps)
    o = o * p["subln"].astype(jnp.float32) * (1.0 - l0)
    o = o.astype(p["w_o"].dtype).reshape(*o.shape[:-2], -1)
    return o @ p["w_o"] + p["b_o"]


def _pair_scores(q, k, cfg):
    """``q`` ``[S, q_pairs, 2d]`` against ``k`` ``[T, kv_pairs, 2d]``:
    the two score maps ``[q_pairs, S, T]`` (float32)."""
    d, r = cfg.head_dim, cfg.q_pairs // cfg.kv_pairs
    k = jnp.repeat(k, r, axis=1)
    s1 = jnp.einsum("sid,tid->ist", q[..., :d], k[..., :d],
                    preferred_element_type=jnp.float32)
    s2 = jnp.einsum("sid,tid->ist", q[..., d:], k[..., d:],
                    preferred_element_type=jnp.float32)
    return s1 / math.sqrt(d), s2 / math.sqrt(d)


def diff_attention_dense(q, k, v, mask, cfg):
    """``(A1, A2)`` of one sequence under a dense ``mask`` ``[S, T]``, in
    float32: :func:`diff_combine` subtracts one from the other, and two
    nearly equal rows rounded to the served type first lose what their
    difference keeps (PERF.md section 4: twice the logits' error)."""
    s1, s2 = _pair_scores(q, k, cfg)
    v = jnp.repeat(v, cfg.q_pairs // cfg.kv_pairs, axis=1)
    out = []
    for s in (s1, s2):
        pr = jax.nn.softmax(jnp.where(mask[None], s, -1e30), -1)
        out.append(jnp.einsum("ist,tid->sid", pr.astype(v.dtype), v,
                              preferred_element_type=jnp.float32))
    return out


def ring_positions(first_free, ring):
    """The position each of ``ring`` rows holds once positions ``<
    first_free`` are written (row ``p % ring`` holds ``p``): the largest
    ``p < first_free`` of the row's residue; negative where none."""
    r = jnp.arange(ring, dtype=jnp.int32)
    last = jnp.asarray(first_free, jnp.int32) - 1
    return last - jnp.mod(last - r, ring)


def window_chunk_attention(q, k, v, ring_k, ring_v, offset, chunk_len,
                           cfg):
    """The window layer over a chunk of one sequence: queries at positions
    ``offset + i`` against the rows as they stood before the chunk
    (``ring_k``, ``ring_v`` ``[ring, kv_pairs, 2d]``) and the chunk's
    own. Returns ``(A1, A2, ring_k, ring_v)`` with the chunk's real rows
    written (row ``p % ring``)."""
    C, ring, W = q.shape[0], ring_k.shape[0], cfg.sliding_window
    offset = jnp.asarray(offset, jnp.int32)
    t = offset + jnp.arange(C, dtype=jnp.int32)
    held = ring_positions(offset, ring)
    key_pos = jnp.concatenate([held, t])
    real = jnp.concatenate([held >= 0, jnp.arange(C) < chunk_len])
    mask = real[None] & (key_pos[None] <= t[:, None]) \
        & (key_pos[None] > t[:, None] - W)
    a1, a2 = diff_attention_dense(
        q, jnp.concatenate([ring_k.astype(k.dtype), k]),
        jnp.concatenate([ring_v.astype(v.dtype), v]), mask, cfg)
    # the rows after the chunk: row r holds the newest real position of
    # its residue, the chunk's where it has one
    now = ring_positions(offset + chunk_len, ring)
    mine = now >= offset
    at = jnp.clip(now - offset, 0, C - 1)
    pick = lambda new, old: jnp.where(
        mine[:, None, None], new[at].astype(old.dtype), old)
    return a1, a2, pick(k, ring_k), pick(v, ring_v)


def final_logits(params, x, cfg):
    h = layer_norm(x, params["lnf_w"], params["lnf_b"], cfg.layer_norm_eps)
    return jnp.einsum("...h,vh->...v", h, params["embed"],
                      preferred_element_type=jnp.float32)


# --------------------------------------------------------------------------
# the full-sequence form, no cache (tests; the engine's programs are in
# serving/phi4flash_engine.py)
# --------------------------------------------------------------------------

def forward_full(params, ids, cfg, use_kernel=False):
    """Logits ``[S, V]`` of one sequence through every layer at every
    position, the mixers in their chunk forms with an empty state."""
    S = ids.shape[0]
    x = params["embed"][ids]
    Di, N, K = cfg.d_inner, cfg.d_state, cfg.d_conv
    s0 = jnp.zeros((N, Di), jnp.float32)
    tail0 = jnp.zeros((K - 1, Di), x.dtype)
    pos = jnp.arange(S)
    causal = pos[None] <= pos[:, None]
    window = causal & (pos[None] > pos[:, None] - cfg.sliding_window)
    take = lambda tree, i: jax.tree.map(lambda a: a[i], tree)
    for i in range(cfg.n_self_pairs):
        pm, pa = (take(params["self_pairs"][k], i) for k in
                  ("mamba", "attn"))
        x, _, _, _ = mamba_chunk(pm, x, s0, tail0, S, cfg, use_kernel)
        x = mlp(pm, x, cfg)
        q, k, v = attn_project(pa, x, cfg)
        a1, a2 = diff_attention_dense(q, k, v, window, cfg)
        x = mlp(pa, x + diff_combine(pa, a1, a2, 2 * i + 1, cfg), cfg)
    x, _, _, m = mamba_chunk(params["l16"], x, s0, tail0, S, cfg,
                             use_kernel)
    x = mlp(params["l16"], x, cfg)
    p17 = params["l17"]
    q, k, v = attn_project(p17, x, cfg)
    a1, a2 = diff_attention_dense(q, k, v, causal, cfg)
    x = mlp(p17, x + diff_combine(p17, a1, a2, cfg.full_layer, cfg), cfg)
    for j in range(cfg.n_cross_pairs):
        pg, pc = (take(params["cross_pairs"][n], j) for n in
                  ("gmu", "cross"))
        x = mlp(pg, memory_unit(pg, x, m, cfg), cfg)
        q, _, _ = attn_project(pc, x, cfg, own_kv=False)
        a1, a2 = diff_attention_dense(q, k, v, causal, cfg)
        x = mlp(pc, x + diff_combine(
            pc, a1, a2, cfg.full_layer + 2 + 2 * j, cfg), cfg)
    return final_logits(params, x, cfg)
