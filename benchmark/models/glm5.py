"""The GLM-5.2 family (``model_type`` ``glm_moe_dsa``), as the benchmark
sees it: one chip's share of a deployment in which 16 chips share each
layer. Beside ``phi4flash.py``, with the same four things:

- the weights, made on the device from the seed **a layer at a time**:
  the served bfloat16 model is the cast of the reference's float32 draw
  (the router stays float32);
- ``build_engine``: the program's ``Glm5ServingEngine`` under
  ``ContinuousBatchingScheduler``, from a configuration file;
- the plain reference, the benchmark's own copy (it imports nothing of the
  program; ``tests/test_glm5.py`` holds it equal to the program's
  ``models/glm5_reference.py``): ``jax.numpy`` float32 at
  ``matmul_precision("highest")``, every layer at every position, the
  index scores of a block of queries against every position, the exact
  top 2,048 of each (``jax.lax.top_k``, ties to the lower position),
  attention over each query's selected rows in the absorbed form (the
  same mathematics as the expanded one; at these widths the expanded
  keys of 65,536 positions are 7.5 GB), each held expert over every token
  weighed by its gate. At the published widths the float32 layers are
  3.2 GB each, so ``served_checks`` makes, uses and frees one at a time;
- the operation counts.

The equations are ``paddle_tpu/models/glm5.py``'s; the configuration
file says what was cut, what was assumed and the deployment.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

SERVING_CONTROL = "fp8"
# planted in the reference in the program's place (``served_checks``):
# what a broken engine would compute
FAULTS = ("selection_ignored", "index_share_ignored", "k_rope_dropped",
          "bias_dropped", "scale_dropped", "shared_expert_dropped")
_NORMS = ("attn_norm", "ffn_norm", "q_norm", "kv_norm", "wi_norm_w", "norm")
_F32 = ("router", "router_bias")
_QUERIES = 128          # the reference's block of queries


# --------------------------------------------------------------------------
# configuration
# --------------------------------------------------------------------------

def load_config(path):
    with open(path) as f:
        cfg = json.load(f)
    for key in ("hidden_size", "num_attention_heads", "q_lora_rank",
                "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim", "index_n_heads", "index_head_dim",
                "index_topk", "intermediate_size", "moe_intermediate_size",
                "n_routed_experts", "router_experts", "expert_offset",
                "num_experts_per_tok", "routed_scaling_factor",
                "rms_norm_eps", "rope_parameters", "vocab_size",
                "served_layers", "indexer_types", "mlp_layer_types",
                "serving", "num_hidden_layers"):
        if key not in cfg:
            raise ValueError(f"{path}: no {key!r}")
    if cfg["scoring_func"] != "sigmoid" or cfg["tie_word_embeddings"] \
            or cfg["n_shared_experts"] != 1 or cfg["n_group"] != 1 \
            or len(cfg["served_layers"]) != cfg["num_hidden_layers"]:
        raise ValueError(f"{path}: sigmoid routing in one group, one shared "
                         f"expert, an untied head and a layer a served index")
    if cfg["indexer_types"][cfg["served_layers"][0]] != "full":
        raise ValueError(f"{path}: the first served layer has an indexer")
    return cfg


def kinds(cfg):
    """``(indexer, mlp)`` type of every served layer."""
    return [(cfg["indexer_types"][l], cfg["mlp_layer_types"][l])
            for l in cfg["served_layers"]]


def full_layers(cfg):
    return [i for i, (ix, _) in enumerate(kinds(cfg)) if ix == "full"]


def share_source(cfg, layer):
    return max(l for l in full_layers(cfg) if l <= layer)


def leaf_shapes(cfg, layer):
    """One served layer's leaves (the program's names and layout)."""
    H, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    ql, kl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    out = {"attn_norm": (H,), "ffn_norm": (H,), "wq_a": (H, ql),
           "q_norm": (ql,), "wq_b": (ql, nh * (dn + dr)),
           "wkv_a": (H, kl + dr), "kv_norm": (kl,),
           "wkv_b": (kl, nh * (dn + dv)), "wo": (nh * dv, H)}
    index, mlp = kinds(cfg)[layer]
    if index == "full":
        dI = cfg["index_head_dim"]
        out.update(wi_q=(ql, cfg["index_n_heads"] * dI), wi_k=(H, dI),
                   wi_norm_w=(dI,), wi_norm_b=(dI,),
                   wi_w=(H, cfg["index_n_heads"]))
    if mlp == "dense":
        F = cfg["intermediate_size"]
        out.update(w_gate_up=(H, 2 * F), w_down=(F, H))
    else:
        E, Fe = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
        out.update(router=(H, cfg["router_experts"]),
                   router_bias=(cfg["router_experts"],),
                   experts_gate_up=(E, H, 2 * Fe), experts_down=(E, Fe, H),
                   shared_gate_up=(H, 2 * Fe), shared_down=(Fe, H))
    return out


def param_count(cfg, layers=None):
    """Parameters of the served layers (those of ``layers``, else all and
    the tables)."""
    n = sum(math.prod(s) for l in (range(cfg["num_hidden_layers"])
                                   if layers is None else layers)
            for s in leaf_shapes(cfg, l).values())
    if layers is None:
        n += 2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]
    return n


def program_config(cfg):
    from paddle_tpu.models.glm5 import Glm5Config
    k = kinds(cfg)
    return Glm5Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_attention_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], index_n_heads=cfg["index_n_heads"],
        index_head_dim=cfg["index_head_dim"],
        index_rope_dim=cfg["index_head_dim"] // 2,
        index_topk=cfg["index_topk"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        n_routed_experts=cfg["router_experts"],
        experts_held=cfg["n_routed_experts"],
        expert_offset=cfg["expert_offset"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        norm_topk_prob=cfg["norm_topk_prob"],
        rms_norm_eps=cfg["rms_norm_eps"],
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        max_position_embeddings=cfg["max_position_embeddings"],
        mlp_layer_types=tuple(m for _, m in k),
        indexer_types=tuple(i for i, _ in k),
        initializer_range=cfg.get("initializer_range", 0.02))


# --------------------------------------------------------------------------
# operation counts
# --------------------------------------------------------------------------

def _prompt_runs(ctx, chunk):
    """How many leading entries of ``ctx`` are prompt positions (see
    ``phi4flash.py``: every position of every chunk first, a chunk a run
    that starts one past a multiple of the chunk, then the ticks)."""
    i, n = 0, len(ctx)
    while i < n and (ctx[i] - 1) % chunk == 0:
        j = i + 1
        while j < n and j - i < chunk and ctx[j] == ctx[j - 1] + 1:
            j += 1
        i = j
    return i


def attention_flops(cfg, rows):
    """A query's latent attention over ``rows`` selected rows, every
    head: the score (576) and the weighted rows (512), 2 operations each."""
    return cfg["num_attention_heads"] * 2.0 * (
        cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
        + cfg["kv_lora_rank"]) * rows


def index_flops(cfg, keys):
    """A query's index score against ``keys`` keys: every head's dot."""
    return cfg["index_n_heads"] * 2.0 * cfg["index_head_dim"] * keys


def serve_flops(cfg, context_lens, prompt_positions=None):
    """Forward FLOPs of the positions processed at ``context_lens``: 2 x
    the parameters a token is multiplied by (attention, indexers, the
    dense MLP, the shared experts, the router, and of the held experts
    the share a token's 8 choices give them on average: 8 x 16 / 256 =
    0.5 an expert layer), attention over ``min(context, 2048)`` rows in
    every layer, the index score over ``context`` keys in every layer
    with an indexer, and the head at a produced token (a prompt's chunk
    runs it at its last position alone)."""
    ctx = [int(c) for c in context_lens]
    if prompt_positions is None:
        prompt_positions = _prompt_runs(ctx, cfg["serving"]["prefill_chunk"])
    L = cfg["num_hidden_layers"]
    H, Fe = cfg["hidden_size"], cfg["moe_intermediate_size"]
    per_token = param_count(cfg, range(L))
    n_moe = sum(m == "sparse" for _, m in kinds(cfg))
    expert = 3 * H * Fe
    per_token -= n_moe * cfg["n_routed_experts"] * expert
    per_token += n_moe * expert * cfg["num_experts_per_tok"] \
        * cfg["n_routed_experts"] / cfg["router_experts"]
    c = np.asarray(ctx, np.float64)
    produced = np.arange(len(ctx)) >= prompt_positions
    flops = 2.0 * per_token * len(ctx) \
        + L * attention_flops(cfg, np.minimum(c, cfg["index_topk"]).sum()) \
        + len(full_layers(cfg)) * index_flops(cfg, c.sum()) \
        + 2.0 * H * cfg["vocab_size"] * produced.sum()
    return float(flops)


# --------------------------------------------------------------------------
# weights from the seed, a layer at a time
# --------------------------------------------------------------------------

def _key(seed):
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed % (1 << 31)),
                              seed // (1 << 31))


_LEAVES = sorted((
    "embed", "norm", "head", "attn_norm", "ffn_norm", "wq_a", "q_norm",
    "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo", "wi_q", "wi_k", "wi_norm_w",
    "wi_norm_b", "wi_w", "w_gate_up", "w_down", "router", "router_bias",
    "experts_gate_up", "experts_down", "shared_gate_up", "shared_down"))


def _leaf_key(seed, name, layer=0):
    return jax.random.fold_in(jax.random.fold_in(
        _key(seed), _LEAVES.index(name)), layer)


@functools.partial(jax.jit, static_argnames=("name", "shape", "std",
                                             "dtype"))
def _make(key, name, shape, std, dtype):
    """One leaf: matrices N(0, std), norm scales 1 + N(0, std), the
    LayerNorm bias and the router's bias N(0, 0.1); the router in float32
    whatever ``dtype``."""
    noise = jax.random.normal(key, shape, jnp.float32)
    if name in _NORMS:
        w = 1.0 + std * noise
    elif name in ("wi_norm_b", "router_bias"):
        w = 0.1 * noise
    else:
        w = std * noise
    return w if name in _F32 else w.astype(jnp.dtype(dtype))


def _std(cfg):
    return cfg.get("initializer_range", 0.02)


def layer_weights(cfg, seed, layer, dtype=jnp.float32):
    """One served layer's leaves: the same draw as ``init_weights`` makes
    of it."""
    return {k: _make(_leaf_key(seed, k, layer), k, s, _std(cfg),
                     str(jnp.dtype(dtype)))
            for k, s in leaf_shapes(cfg, layer).items()}


def table_weights(cfg, seed, dtype=jnp.float32):
    H, V, dt = cfg["hidden_size"], cfg["vocab_size"], str(jnp.dtype(dtype))
    return {name: _make(_leaf_key(seed, name), name, shape, _std(cfg), dt)
            for name, shape in (("embed", (V, H)), ("norm", (H,)),
                                ("head", (H, V)))}


def init_weights(cfg, seed, dtype=None):
    """With a ``dtype``: the program's layout (the tables and a list of
    layers), made on the device a layer at a time. Without one (the
    reference's, as the driver asks for them): the seed alone; a layer is
    made when it is needed."""
    if dtype is None:
        return {"seed": int(seed)}
    out = table_weights(cfg, seed, dtype)
    out["layers"] = [layer_weights(cfg, seed, l, dtype)
                     for l in range(cfg["num_hidden_layers"])]
    return out


def whole_weights(cfg, seed):
    """Every layer at once in float32 (a size that fits: the tests)."""
    return init_weights(cfg, seed, jnp.float32)


# --------------------------------------------------------------------------
# the program's objects
# --------------------------------------------------------------------------

def build_engine(cfg, deploy, seed):
    """``Glm5ServingEngine`` as the configuration's ``serving`` group and
    the traffic file deploy it, the weights made from the seed in the
    served type. The program is imported first: a tree without it fails
    here, before a weight is made."""
    from paddle_tpu.serving.glm5_engine import Glm5ServingEngine
    s = cfg["serving"]
    if s["weight_dtype"] != s["kv_dtype"] or s["sampling"] != "greedy" \
            or s["prefix_cache"]:
        raise ValueError("one served type, greedy, no prefix cache")
    return Glm5ServingEngine(
        init_weights(cfg, seed, dtype=s["weight_dtype"]),
        program_config(cfg), page_size=s["page_size"],
        num_pages=deploy["pool_tokens"] // s["page_size"] + 1,
        max_seq_len=deploy["max_seq_len"],
        decode_buckets=tuple(deploy["decode_buckets"]),
        prefill_chunk=s["prefill_chunk"], prefix_cache=False,
        selection_every=deploy["selection_every"])


# --------------------------------------------------------------------------
# the plain reference
# --------------------------------------------------------------------------

def _lower(x, mode):
    """Round a matmul operand as the control's precision would hold it."""
    if mode is None:
        return x
    kind = {"bf16": jnp.bfloat16, "fp8": jnp.float8_e4m3fn}[mode]
    return x.astype(kind).astype(jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _ln(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def _rope(x, pos, theta):
    """Interleaved pairs ``(2i, 2i+1)`` of the last axis; ``pos`` ``[S]``
    broadcast over the middle axes."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32).reshape((-1,) + (1,) * (x.ndim - 1)) * inv
    a, b = x[..., 0::2], x[..., 1::2]
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.stack([a * c - b * s, a * s + b * c], -1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("cfgt", "mode"))
def _keys(p, x, cfgt, mode):
    """What every position offers the queries of a layer over ``x`` ``[S,
    H]``: ``(c_kv [S, 512], k_rope [S, 64], k^I [S, dI] or None)``."""
    c = dict(cfgt)
    lo = functools.partial(_lower, mode=mode)
    eps, kl = c["eps"], c["kl"]
    pos = jnp.arange(x.shape[0])
    a = _rms(x, p["attn_norm"], eps)
    kv = lo(a) @ lo(p["wkv_a"])
    c_kv = _rms(kv[:, :kl], p["kv_norm"], eps)
    k_rope = _rope(kv[:, kl:], pos, c["theta"])
    if "wi_q" not in p:
        return lo(c_kv), lo(k_rope), None
    r = c["dI"] // 2
    ki = _ln(lo(a) @ lo(p["wi_k"]), p["wi_norm_w"], p["wi_norm_b"], eps)
    ki = jnp.concatenate([_rope(ki[:, :r], pos, c["theta"]), ki[:, r:]], -1)
    return lo(c_kv), lo(k_rope), lo(ki)


@functools.partial(jax.jit, static_argnames=("cfgt", "mode", "fault",
                                             "select", "dense"))
def _block(p, x, at, c_kv, k_rope, ki, n, shared, cfgt, mode, fault, select,
           dense):
    """The attention of the queries ``x`` ``[Q, H]`` at positions ``at +
    i``: where ``select``, the exact top ``k`` of their index scores
    against every key (``s <= t`` and ``s < n``, ``jax.lax.top_k``: ties
    to the lower position), else the ``shared`` ``(positions, ok)`` of
    the ``full`` layer below; then the absorbed attention over each
    query's selected rows (over every ``s <= t`` where ``dense``), through
    ``W_vb`` and ``W_o``. Returns ``(out [Q, H], positions, ok)``."""
    c = dict(cfgt)
    lo = functools.partial(_lower, mode=mode)
    eps, nh, dn, dr, kl = c["eps"], c["nh"], c["dn"], c["dr"], c["kl"]
    Q, S = x.shape[0], c_kv.shape[0]
    rows = at + jnp.arange(Q)
    a = _rms(x, p["attn_norm"], eps)
    c_q = _rms(lo(a) @ lo(p["wq_a"]), p["q_norm"], eps)
    q = (lo(c_q) @ lo(p["wq_b"])).reshape(Q, nh, dn + dr)
    q_rope = lo(_rope(q[..., dn:], rows, c["theta"]))
    w_kb = p["wkv_b"].reshape(kl, nh, -1)[..., :dn]
    q_lat = lo(jnp.einsum("qhd,chd->qhc", lo(q[..., :dn]), lo(w_kb)))
    if select:
        nI, dI = c["nI"], c["dI"]
        r = dI // 2
        qi = (lo(c_q) @ lo(p["wi_q"])).reshape(Q, nI, dI)
        qi = lo(jnp.concatenate([_rope(qi[..., :r], rows, c["theta"]),
                                 qi[..., r:]], -1))
        wi = (lo(a) @ lo(p["wi_w"])) / jnp.sqrt(1.0 * nI)
        score = jnp.einsum("qj,qjs->qs", wi, jax.nn.relu(
            jnp.einsum("qjd,sd->qjs", qi, ki))) / jnp.sqrt(1.0 * dI)
        pos = jnp.arange(S)
        mine = (pos[None] <= rows[:, None]) & (pos[None] < n)
        val, idx = jax.lax.top_k(jnp.where(mine, score, -jnp.inf),
                                 min(c["k"], S))
        shared = (idx.astype(jnp.int32), jnp.isfinite(val))
    idx, ok = shared
    scale = (dn + dr) ** -0.5
    if dense:
        score = jnp.einsum("qhc,sc->qhs", q_lat, c_kv)
        if fault != "k_rope_dropped":
            score = score + jnp.einsum("qhr,sr->qhs", q_rope, k_rope)
        live = jnp.arange(S)[None] <= rows[:, None]
        prob = jax.nn.softmax(jnp.where(live[:, None], score * scale,
                                        -jnp.inf), -1)
        o_lat = jnp.einsum("qhs,sc->qhc", lo(prob), c_kv)
    else:
        got = c_kv[idx]                                  # [Q, K, 512]
        score = jnp.einsum("qhc,qkc->qhk", q_lat, got)
        if fault != "k_rope_dropped":
            score = score + jnp.einsum("qhr,qkr->qhk", q_rope, k_rope[idx])
        prob = jax.nn.softmax(jnp.where(ok[:, None], score * scale,
                                        -jnp.inf), -1)
        o_lat = jnp.einsum("qhk,qkc->qhc", lo(prob), got)
    w_vb = p["wkv_b"].reshape(kl, nh, -1)[..., dn:]
    o = jnp.einsum("qhc,chd->qhd", lo(o_lat), lo(w_vb)).reshape(Q, -1)
    return lo(o) @ lo(p["wo"]), idx.astype(jnp.int32), ok


@functools.partial(jax.jit, static_argnames=("cfgt", "mode", "fault"))
def _ffn(p, x, cfgt, mode, fault):
    """The feed-forward part of a layer: the new ``x``, and in an expert
    layer each position's chosen experts ``[S, top-k]`` and their gates
    (None in a dense layer)."""
    c = dict(cfgt)
    lo = functools.partial(_lower, mode=mode)
    a = lo(_rms(x, p["ffn_norm"], c["eps"]))

    def swiglu(gu_w, down_w):
        gu = a @ lo(gu_w)
        F = down_w.shape[-2]
        return lo(jax.nn.silu(gu[:, :F]) * gu[:, F:]) @ lo(down_w)

    if "router" not in p:
        return x + swiglu(p["w_gate_up"], p["w_down"]), None, None
    s = jax.nn.sigmoid(a @ p["router"])
    choice = s if fault == "bias_dropped" else s + p["router_bias"]
    _, idx = jax.lax.top_k(choice, c["topk"])
    chosen = jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], idx] \
        .set(1.0)
    g = s * chosen
    g = g / jnp.sum(g, -1, keepdims=True)
    if fault != "scale_dropped":
        g = g * c["scale"]

    def expert(acc, e):
        gu = a @ lo(p["experts_gate_up"][e])
        F = p["experts_down"].shape[1]
        y = lo(jax.nn.silu(gu[:, :F]) * gu[:, F:]) @ lo(p["experts_down"][e])
        return acc + g[:, c["offset"] + e, None] * y, None

    routed, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                             jnp.arange(p["experts_down"].shape[0]))
    x = x + routed
    if fault != "shared_expert_dropped":
        x = x + swiglu(p["shared_gate_up"], p["shared_down"])
    return x, idx.astype(jnp.int32), jnp.take_along_axis(g, idx, 1)


def _cfgt(cfg):
    return tuple(sorted(dict(
        eps=cfg["rms_norm_eps"], nh=cfg["num_attention_heads"],
        dn=cfg["qk_nope_head_dim"], dr=cfg["qk_rope_head_dim"],
        kl=cfg["kv_lora_rank"], nI=cfg["index_n_heads"],
        dI=cfg["index_head_dim"], k=cfg["index_topk"],
        theta=float(cfg["rope_parameters"]["rope_theta"]),
        topk=cfg["num_experts_per_tok"],
        scale=cfg["routed_scaling_factor"],
        offset=cfg["expert_offset"]).items()))


def _layer(cfg, p, x, layer, n, selected, routes, mode, fault):
    """One layer over a padded sequence ``x`` ``[S, H]`` of ``n`` real
    rows, a block of ``_QUERIES`` queries at a time: fills
    ``selected[layer]`` at a ``full`` layer with ``(positions [S, k], ok
    [S, k])`` and ``routes[layer]`` at an expert layer with ``(experts
    [S, top-k], gates [S, top-k])``, and returns the new ``x``."""
    with jax.default_matmul_precision("highest"):
        S = x.shape[0]
        full = kinds(cfg)[layer][0] == "full"
        dense = fault == "selection_ignored" or (
            fault == "index_share_ignored" and not full)
        c_kv, k_rope, ki = _keys(p, x, _cfgt(cfg), mode)
        source = selected.get(share_source(cfg, layer))
        outs, idx, ok = [], [], []
        for at in range(0, S, _QUERIES):
            shared = None if full else tuple(
                v[at:at + _QUERIES] for v in source)
            o, i, g = _block(p, x[at:at + _QUERIES], jnp.int32(at), c_kv,
                             k_rope, ki, jnp.int32(n), shared, _cfgt(cfg),
                             mode, fault, full, dense)
            outs.append(o)
            idx.append(i)
            ok.append(g)
        if full:
            selected[layer] = (jnp.concatenate(idx), jnp.concatenate(ok))
        x, experts, gates = _ffn(p, x + jnp.concatenate(outs), _cfgt(cfg),
                                 mode, fault)
        if experts is not None:
            routes[layer] = (experts, gates)
        return x


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _head(tables, x, eps, mode):
    with jax.default_matmul_precision("highest"):
        return _lower(_rms(x, tables["norm"], eps), mode) \
            @ _lower(tables["head"], mode)


_PADDED = [0]       # the widest bucket so far: few shapes compile


def _forward(cfg, seed, ids, n, variants, whole=None):
    """The last layer's output, each ``full`` layer's selections and each
    expert layer's routing of one padded sequence under each of
    ``variants`` (``(mode, fault)``): ``[(x, selected, routes)]``, a layer
    of weights made, used by every variant and freed before the next
    (``whole`` stands in for the seed where the weights fit)."""
    tables = table_weights(cfg, seed) if whole is None else whole
    x0 = tables["embed"][jnp.asarray(ids, jnp.int32)]
    state = [(x0, {}, {}) for _ in variants]
    for l in range(cfg["num_hidden_layers"]):
        p = layer_weights(cfg, seed, l) if whole is None \
            else whole["layers"][l]
        state = [(_layer(cfg, p, x, l, n, sel, routes, mode, fault), sel,
                  routes)
                 for (x, sel, routes), (mode, fault) in zip(state, variants)]
        del p
    return state


def _read(tables, x, rows, eps, mode, tokens=None, pad=512):
    """Of the logits at ``rows`` of ``x``, a block of rows at a time: the
    best logit, the best token and the logit of each row's ``tokens``
    entry, as NumPy vectors."""
    n = len(rows)
    tokens = np.zeros(n, np.int64) if tokens is None else np.asarray(tokens)
    out = []
    for at in range(0, n, pad):
        fill = max(0, at + pad - n)
        block = lambda v: jnp.asarray(np.concatenate(
            [v[at:at + pad], np.zeros(fill, np.int64)]), jnp.int32)
        logits = _head(tables, x[block(rows)], eps, mode)
        held = jnp.take_along_axis(logits, block(tokens)[:, None], 1)[:, 0]
        out.append([np.asarray(v)[:pad - fill] for v in
                    (logits.max(-1), logits.argmax(-1), held)])
    return tuple(np.concatenate(v) for v in zip(*out))


def selection_miss(have, want):
    """1 less the share of two selections that agree, over their union
    (an empty pair agrees)."""
    have, want = set(have), set(want)
    union = have | want
    return 1.0 - len(have & want) / len(union) if union else 0.0


def router_gap(have, want):
    """How far two routings of a token apart lie: the sum over every
    routed expert of the difference of the two gates, an expert not
    chosen at 0. ``have`` and ``want`` are ``(experts [top-k], gates
    [top-k])``. 0 where both chose alike; where one chose otherwise, the
    gates of the experts only one chose; with the scale 2.5 dropped
    from one, 1.5."""
    gap = {}
    for sign, (experts, gates) in ((1.0, have), (-1.0, want)):
        for e, g in zip(np.asarray(experts).tolist(),
                        np.asarray(gates, np.float64).tolist()):
            gap[e] = gap.get(e, 0.0) + sign * g
    return float(sum(abs(v) for v in gap.values()))


def served_checks(cfg, weights, prompt, served, probes=(), mode=None,
                  fault=None, whole=None):
    """One forward pass over ``prompt + served``, every layer at every
    position. Returns ``(gaps, misses, routes)``: for each served token,
    how far its reference logit lies below the reference's best at that
    position; for each ``probes`` entry ``(position, selections [layers,
    k], experts [expert layers, top-k], gates [expert layers, top-k])``
    (the positions each layer's attention read in the program, ``-1``
    past a selection, and each expert layer's choice and gates there),
    the :func:`selection_miss` of each layer's against the reference's at
    that position (a ``shared`` layer's is the selection of the ``full``
    layer below it) and the :func:`router_gap` of each expert layer's.
    With ``mode`` (the control) or ``fault`` (one of ``FAULTS``) the
    reference so altered stands in the program's place: the token it
    puts first at every position of the prompt and of the served tokens,
    and the selections and routing it makes at the probes' positions,
    are the ones held against the sound reference.

    ``weights`` is ``init_weights(cfg, seed)``: the seed. The sequence is
    padded to a bucket of its own (4,096s, never narrower than an earlier
    call's, so that a run's requests share one compiled shape)."""
    stand_in = mode is not None or fault is not None
    return stand_in_checks(cfg, weights, prompt, served, probes,
                           [(mode, fault) if stand_in else None], whole)[0]


def stand_in_checks(cfg, weights, prompt, served, probes, stand_ins,
                    whole=None):
    """:func:`served_checks` for several stand-ins over one sound pass:
    each of ``stand_ins`` is None (the program's own tokens, selections
    and routing) or ``(mode, fault)``; a ``(gaps, misses, routes)``
    each."""
    seed = weights["seed"]
    n, m = len(prompt), len(served)
    S = max(-(-(n + m) // 4096) * 4096, _PADDED[0]) if whole is None \
        else -(-(n + m) // _QUERIES) * _QUERIES
    _PADDED[0] = S if whole is None else _PADDED[0]
    ids = np.zeros(S, np.int64)
    ids[:n] = prompt
    ids[n:n + m] = served
    variants = [(None, None)] + [v for v in stand_ins if v is not None]
    states = _forward(cfg, seed, ids, n + m, variants, whole)
    tables = table_weights(cfg, seed) if whole is None else whole
    eps = cfg["rms_norm_eps"]
    sel = lambda state, l, at: np.asarray(state[1][l][0][at])[
        np.asarray(state[1][l][1][at])]
    out, k = [], 1
    for v in stand_ins:
        if v is None:
            rows = np.arange(n - 1, n + m - 1)
            tokens = np.asarray(served, np.int64)
        else:
            (mode, fault), alt = v, states[k]
            k += 1
            rows = np.arange(0, n + m - 1)
            _, tokens, _ = _read(tables, alt[0], rows, eps, mode)
        best, _, held = _read(tables, states[0][0], rows, eps, None, tokens)
        misses, gaps = [], []
        for at, chosen, experts, gates in probes:
            for i, l in enumerate(sorted(states[0][2])):
                want = tuple(np.asarray(r[at]) for r in states[0][2][l])
                have = (experts[i], gates[i]) if v is None else tuple(
                    np.asarray(r[at]) for r in alt[2][l])
                gaps.append(router_gap(have, want))
            for l in range(cfg["num_hidden_layers"]):
                src = share_source(cfg, l)
                if v is None:
                    have = [x for x in np.asarray(chosen[l]) if x >= 0]
                elif v[1] == "selection_ignored" or (
                        v[1] == "index_share_ignored" and src != l):
                    have = range(at + 1)
                else:
                    have = sel(alt, src, at)
                misses.append(selection_miss(
                    [int(x) for x in have],
                    [int(x) for x in sel(states[0], src, at)]))
        out.append((best - held, np.asarray(misses, np.float64),
                    np.asarray(gaps, np.float64)))
    return out


def reference_logits(cfg, whole, ids):
    """Float32 logits ``[S, V]`` of one sequence from weights that fit
    whole, each ``full`` layer's selection ``(positions, ok)`` and each
    expert layer's routing ``(experts, gates)``: what the tests hold
    against the program's reference."""
    S = len(ids)
    pad = -(-S // _QUERIES) * _QUERIES
    ids = np.concatenate([np.asarray(ids), np.zeros(pad - S, np.int64)])
    ((x, sel, routes),) = _forward(cfg, None, ids, S, [(None, None)],
                                   whole)
    cut = lambda d: {l: tuple(np.asarray(v)[:S] for v in s)
                     for l, s in d.items()}
    return _head(whole, x, cfg["rms_norm_eps"], None)[:S], cut(sel), \
        cut(routes)
