"""The Phi-4-mini-flash family (``model_type`` ``phi4flash``, "SambaY",
arXiv:2507.06607), as the benchmark sees it. Beside ``gpt.py`` and
``sdar.py``, with the same four things:

- the weights, made on the device from the seed **a layer at a time**:
  the served bfloat16 model is the cast of the reference's float32 draw;
- ``build_engine``: the program's ``Phi4FlashServingEngine`` under
  ``ContinuousBatchingScheduler``, from a configuration file;
- the plain reference, the benchmark's own copy (it imports nothing of
  the program; ``tests/test_serving_hybrid.py`` holds it equal to the
  program's ``models/phi4flash_reference.py``): ``jax.numpy`` float32 at
  ``matmul_precision("highest")``, every layer at every position, the
  state-space scan a ``lax.scan`` a position at a time, attention a
  dense masked softmax a pair of heads at a time. At the published
  widths the float32 model is 15.4 GB, so ``served_token_gaps`` makes,
  uses and frees one layer at a time;
- the operation counts.

The equations (``d`` = hidden, ``LN`` = LayerNorm with scale and bias,
``L`` layers, ``M = L / 2``): every layer is ``h += Mix_l(LN1(h))``, ``h
+= MLP(LN2(h))`` with ``MLP(a) = (u * silu(g)) W_2``, ``[g, u] = a W_1``,
no bias; a final ``LN`` and the tied head ``h E^T``; no position is
added anywhere. ``Mix_l``: Mamba at even ``l <= M`` (``[x, z] = a W_in``;
``x = silu(conv_4(x) + b)``; ``[dr, B, C] = x W_x``; ``dt = softplus(dr
W_dt + b_dt)``; ``s_t = exp(dt_t A) s_{t-1} + (dt_t x_t) (x) B_t`` from
``s = 0``, ``A = -exp(A_log)``; ``y_t = s_t C_t + D x_t``; ``(y *
silu(z)) W_out``; **layer M keeps ``y`` as the memory**); differential
attention under a window of ``sliding_window`` keys with the query's own
at odd ``l < M``; the same under the causal mask at ``l = M + 1``, whose
``k``, ``v`` the cross layers read; a gated memory unit ``(silu(a W_1) *
memory) W_2`` at even ``l >= M + 2``; cross-attention (``q`` only) onto
layer ``M + 1``'s ``k``, ``v`` at odd ``l >= M + 3``. Differential
attention: heads pair up, ``(q1, q2)_i`` reads ``(k1, k2)_j``, ``[v1 ;
v2]_j`` with ``j = i // (query pairs / KV pairs)``; ``A1 = softmax(q1
k1^T / sqrt(head)) V``, ``A2`` likewise; ``lam = exp(lq1 . lk1) - exp(lq2
. lk2) + lam0``, ``lam0 = 0.8 - 0.6 exp(-0.3 l)``; ``RMSNorm(A1 - lam A2)
(1 - lam0)`` with a learned scale; ``W_o`` with bias.

Departures from the published description: none known in the
mathematics; what the config does not give is the configuration file's
``assumed``.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

SERVING_CONTROL = "fp8"
# planted in the reference in the program's place (``served_token_gaps``):
# what a broken engine would compute
FAULTS = ("chunk_state_zeroed", "slot_not_reset", "window_ignored",
          "cross_stale", "memory_after_gate", "no_lambda")
_ONES = ("ln1_w", "ln2_w", "lnf_w", "subln")


# --------------------------------------------------------------------------
# configuration
# --------------------------------------------------------------------------

def load_config(path):
    with open(path) as f:
        cfg = json.load(f)
    for key in ("hidden_size", "num_hidden_layers", "num_attention_heads",
                "num_key_value_heads", "intermediate_size", "sliding_window",
                "mb_per_layer", "layer_norm_eps", "vocab_size",
                "max_position_embeddings", "tie_word_embeddings",
                "state_space", "initializer_range", "serving"):
        if key not in cfg:
            raise ValueError(f"{path}: no {key!r}")
    if not cfg["tie_word_embeddings"] or cfg.get("mlp_bias") \
            or cfg.get("lm_head_bias") or cfg["mb_per_layer"] != 2:
        raise ValueError(f"{path}: the head is tied, the MLP and the head "
                         f"carry no bias and every second layer is Mamba, "
                         f"in this family")
    if cfg["num_hidden_layers"] % 4 or cfg["num_hidden_layers"] < 8:
        raise ValueError(f"{path}: num_hidden_layers is a multiple of 4, "
                         f"at least 8")
    return cfg


def dims(cfg):
    """The sizes the config gives and those it leaves to ``state_space``."""
    H, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    ss = cfg["state_space"]
    return dict(
        H=H, F=cfg["intermediate_size"], V=cfg["vocab_size"], nh=nh,
        nkv=cfg["num_key_value_heads"], d=H // nh,
        Di=ss["expand"] * H, N=ss["d_state"], K=ss["d_conv"],
        R=ss.get("dt_rank") or math.ceil(H / 16),
        L=cfg["num_hidden_layers"], W=cfg["sliding_window"])


def layer_kinds(cfg):
    L = cfg["num_hidden_layers"]
    M = L // 2
    return ["mamba" if l % 2 == 0 and l <= M else "window" if l < M else
            "full" if l == M + 1 else "gmu" if l % 2 == 0 else "cross"
            for l in range(L)]


def leaf_shapes(cfg, kind):
    """One layer's leaves, by the kind of its mixer."""
    s = dims(cfg)
    H, F, Di, N, R, d = s["H"], s["F"], s["Di"], s["N"], s["R"], s["d"]
    out = {"ln1_w": (H,), "ln1_b": (H,), "ln2_w": (H,), "ln2_b": (H,),
           "w1": (H, 2 * F), "w2": (F, H)}
    if kind == "mamba":
        out.update(w_in=(H, 2 * Di), conv_w=(s["K"], Di), conv_b=(Di,),
                   w_x=(Di, R + 2 * N), w_dt=(R, Di), b_dt=(Di,),
                   A_log=(N, Di), D=(Di,), w_out=(Di, H))
    elif kind == "gmu":
        out.update(w_g1=(H, Di), w_g2=(Di, H))
    else:
        nq = s["nh"] * d
        n = nq + (0 if kind == "cross" else 2 * s["nkv"] * d)
        out.update(w_qkv=(H, n), b_qkv=(n,), w_o=(nq, H), b_o=(H,),
                   lam=(4, d), subln=(2 * d,))
    return out


def param_count(cfg, kinds=None):
    """Parameters of the layers of those kinds (all, and the table, where
    none is named)."""
    n = sum(math.prod(s) for k in layer_kinds(cfg)
            if kinds is None or k in kinds
            for s in leaf_shapes(cfg, k).values())
    if kinds is None:
        n += cfg["vocab_size"] * cfg["hidden_size"] + 2 * cfg["hidden_size"]
    return n


# --------------------------------------------------------------------------
# operation counts
# --------------------------------------------------------------------------

def _prompt_runs(ctx, chunk):
    """How many leading entries of ``ctx`` are prompt positions.
    ``metrics/step_mfu.serve.py`` hands one list: first every position of
    every prefill chunk of the window (a chunk that began at position
    ``at`` gives ``at + 1, at + 2, ...``, and ``at`` is a multiple of the
    chunk), then the live lengths of every decode tick. A leading run
    that starts one past a multiple of the chunk and counts up by one,
    for at most a chunk, is a chunk."""
    i, n = 0, len(ctx)
    while i < n and (ctx[i] - 1) % chunk == 0:
        j = i + 1
        while j < n and j - i < chunk and ctx[j] == ctx[j - 1] + 1:
            j += 1
        i = j
    return i


def serve_flops(cfg, context_lens, prompt_positions=None):
    """Forward FLOPs of the positions processed at ``context_lens``.

    A **prompt** position runs layers 0..M+1 only (the cross-decoder runs
    at a prompt's last position alone): 2 x their matmul parameters, the
    window layers' and the full layer's attention over ``min(context,
    window)`` and ``context`` keys, the scans. A **produced** token runs
    every layer and the head, the shared K/V counted for each of its
    readers (the full layer and every cross layer). ``prompt_positions``
    says how many leading entries are prompt positions; where the caller
    gives none they are told from the list's own order
    (:func:`_prompt_runs`)."""
    s = dims(cfg)
    ctx = [int(c) for c in context_lens]
    if prompt_positions is None:
        prompt_positions = _prompt_runs(ctx, cfg["serving"]["prefill_chunk"])
    kinds = layer_kinds(cfg)
    n_window, n_cross = kinds.count("window"), kinds.count("cross")
    self_mm = param_count(cfg, ("mamba", "window", "full"))
    cross_mm = param_count(cfg, ("gmu", "cross")) \
        + cfg["vocab_size"] * cfg["hidden_size"]
    # a query head against a key (2 x head) and onto a pair of value
    # heads (2 x 2 head), every query head
    per_key = s["nh"] * 6.0 * s["d"]
    scan = kinds.count("mamba") * 9.0 * s["Di"] * s["N"]
    c = np.asarray(ctx, np.float64)
    produced = np.arange(len(ctx)) >= prompt_positions
    flops = (2.0 * self_mm + scan) * len(ctx) \
        + per_key * (n_window * np.minimum(c, s["W"]).sum() + c.sum()) \
        + 2.0 * cross_mm * produced.sum() \
        + per_key * n_cross * c[produced].sum()
    return float(flops)


# --------------------------------------------------------------------------
# weights from the seed, a layer at a time
# --------------------------------------------------------------------------

def _key(seed):
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed % (1 << 31)),
                              seed // (1 << 31))


_LEAVES = sorted((
    "embed", "lnf_w", "lnf_b", "ln1_w", "ln1_b", "ln2_w", "ln2_b", "w1",
    "w2", "w_in", "conv_w", "conv_b", "w_x", "w_dt", "b_dt", "A_log", "D",
    "w_out", "w_g1", "w_g2", "w_qkv", "b_qkv", "w_o", "b_o", "lam", "subln"))


def _leaf_key(seed, name, layer=0):
    return jax.random.fold_in(jax.random.fold_in(
        _key(seed), _LEAVES.index(name)), layer)


@functools.partial(jax.jit, static_argnames=("name", "shape", "std",
                                             "dtype"))
def _make(key, name, shape, std, dtype):
    """One leaf: matrices N(0, std); norm scales and ``D`` 1 + N(0, std);
    ``lam`` N(0, 0.1); and Mamba's published initialisation where N(0,
    std) would give a state that forgets in two positions or a scan whose
    input is nothing: ``A_log = log(1..N)`` a channel plus noise, ``b_dt``
    the inverse softplus of a log-uniform step in [1e-3, 1e-1], the
    convolution U(+-1/sqrt(d_conv)) (under N(0, 0.02) the scan's input is
    0.02 and the state 1e-6 of ``y``: a state zeroed at every chunk
    boundary moved the tiny model's logits by 1e-7)."""
    noise = std * jax.random.normal(key, shape, jnp.float32)
    if name in _ONES or name == "D":
        w = 1.0 + noise
    elif name == "lam":
        w = noise * (0.1 / std)
    elif name == "conv_w":
        w = jax.random.uniform(key, shape, jnp.float32, -1.0, 1.0) \
            / math.sqrt(shape[0])
    elif name == "A_log":
        w = jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32))[:, None] \
            + noise
    elif name == "b_dt":
        u = jax.random.uniform(jax.random.fold_in(key, 1), shape)
        dt = jnp.exp(u * (math.log(1e-1) - math.log(1e-3))
                     + math.log(1e-3))
        w = dt + jnp.log(-jnp.expm1(-dt))
    else:
        w = noise
    return w.astype(jnp.dtype(dtype))


def layer_weights(cfg, seed, layer, dtype=jnp.float32):
    """``(kind, leaves)`` of one layer: the same draw as ``init_weights``
    makes of it."""
    kind = layer_kinds(cfg)[layer]
    return kind, {k: _make(_leaf_key(seed, k, layer), k, s,
                           cfg["initializer_range"], str(jnp.dtype(dtype)))
                  for k, s in leaf_shapes(cfg, kind).items()}


def table_weights(cfg, seed, dtype=jnp.float32):
    H, std = cfg["hidden_size"], cfg["initializer_range"]
    dt = str(jnp.dtype(dtype))
    return {"embed": _make(_leaf_key(seed, "embed"), "embed",
                           (cfg["vocab_size"], H), std, dt),
            "lnf_w": _make(_leaf_key(seed, "lnf_w"), "lnf_w", (H,), std, dt),
            "lnf_b": _make(_leaf_key(seed, "lnf_b"), "lnf_b", (H,), std, dt)}


def init_weights(cfg, seed, dtype=None):
    """With a ``dtype``: every weight in the program's stacked layout
    (``self_pairs``, ``l16``, ``l17``, ``cross_pairs``, the tables), made
    on the device a layer at a time. Without one (the reference's, as the
    driver asks for them): the seed alone, since the float32 model does
    not fit beside its own activations and ``served_token_gaps`` makes a
    layer when it needs it."""
    if dtype is None:
        return {"seed": int(seed)}
    L = cfg["num_hidden_layers"]
    M = L // 2
    stack = lambda layers: jax.tree.map(
        lambda *a: jnp.stack(a), *[layer_weights(cfg, seed, l, dtype)[1]
                                   for l in layers])
    out = table_weights(cfg, seed, dtype)
    out["self_pairs"] = {"mamba": stack(range(0, M, 2)),
                         "attn": stack(range(1, M, 2))}
    out["l16"] = layer_weights(cfg, seed, M, dtype)[1]
    out["l17"] = layer_weights(cfg, seed, M + 1, dtype)[1]
    out["cross_pairs"] = {"gmu": stack(range(M + 2, L, 2)),
                          "cross": stack(range(M + 3, L, 2))}
    return out


# --------------------------------------------------------------------------
# the program's objects
# --------------------------------------------------------------------------

def program_config(cfg):
    from paddle_tpu.models.phi4flash import Phi4FlashConfig
    ss = cfg["state_space"]
    return Phi4FlashConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        intermediate_size=cfg["intermediate_size"],
        sliding_window=cfg["sliding_window"],
        mb_per_layer=cfg["mb_per_layer"],
        layer_norm_eps=cfg["layer_norm_eps"],
        max_position_embeddings=cfg["max_position_embeddings"],
        initializer_range=cfg["initializer_range"],
        d_state=ss["d_state"], d_conv=ss["d_conv"], expand=ss["expand"],
        dt_rank=ss.get("dt_rank") or 0)


def build_engine(cfg, deploy, seed):
    """``Phi4FlashServingEngine`` as the configuration's ``serving`` group
    deploys it, the weights made from the seed in the served type."""
    from paddle_tpu.serving import Phi4FlashServingEngine
    s = cfg["serving"]
    if s["weight_dtype"] != s["kv_dtype"] or s["sampling"] != "greedy" \
            or s["state_dtype"] != "float32" or s["prefix_cache"]:
        raise ValueError("one served type, a float32 state, greedy, no "
                         "prefix cache")
    return Phi4FlashServingEngine(
        init_weights(cfg, seed, dtype=s["weight_dtype"]),
        program_config(cfg), page_size=s["page_size"],
        num_pages=deploy["pool_tokens"] // s["page_size"] + 1,
        max_seq_len=deploy["max_seq_len"],
        decode_buckets=tuple(deploy["decode_buckets"]),
        prefill_chunk=s["prefill_chunk"], prefix_cache=False)


# --------------------------------------------------------------------------
# the plain reference
# --------------------------------------------------------------------------

def _lower(x, mode):
    """Round a matmul operand as the control's precision would hold it."""
    if mode is None:
        return x
    kind = {"bf16": jnp.bfloat16, "fp8": jnp.float8_e4m3fn}[mode]
    return x.astype(kind).astype(jnp.float32)


def _ln(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def _mamba(p, a, s, lo, fault, chunk):
    """``(mixer output, the memory)`` over ``a`` ``[S, H]``."""
    S, K, R, N = a.shape[0], s["K"], s["R"], s["N"]
    x, z = jnp.split(lo(a) @ lo(p["w_in"]), 2, axis=-1)
    # a slot that was not reset holds what the sequence before left: here
    # the sequence itself, run once before
    before = x[S - (K - 1):] if fault == "slot_not_reset" \
        else jnp.zeros((K - 1, x.shape[1]), x.dtype)
    xp = jnp.concatenate([before, x])
    x = jax.nn.silu(sum(xp[k:k + S] * p["conv_w"][k] for k in range(K))
                    + p["conv_b"])
    proj = lo(x) @ lo(p["w_x"])
    dr, B, C = proj[:, :R], proj[:, R:R + N], proj[:, R + N:]
    dt = jax.nn.softplus(lo(dr) @ lo(p["w_dt"]) + p["b_dt"])
    A = -jnp.exp(p["A_log"])                            # [N, Di]

    def step(st, xs):
        x_t, dt_t, b_t, c_t, t = xs
        if fault == "chunk_state_zeroed":
            st = jnp.where((t % chunk == 0) & (t > 0), 0.0, st)
        st = jnp.exp(dt_t[None] * A) * st \
            + (dt_t * x_t)[None] * b_t[:, None]
        return st, jnp.sum(st * c_t[:, None], 0)

    xs = (x, dt, B, C, jnp.arange(S, dtype=jnp.int32))
    s0 = jnp.zeros_like(A)
    if fault == "slot_not_reset":
        s0, _ = jax.lax.scan(step, s0, xs)
    _, y = jax.lax.scan(step, s0, xs)
    y = y + p["D"] * x
    gated = y * jax.nn.silu(z)
    return lo(gated) @ lo(p["w_out"]), \
        gated if fault == "memory_after_gate" else y


def _diff_attention(p, q, k, v, mask, layer, s, eps, lo, fault):
    """``q`` ``[S, nh, d]``, ``k``, ``v`` ``[S, nkv, d]``: a pair of query
    heads at a time against its pair of KV heads."""
    S, d = q.shape[0], s["d"]
    r = (s["nh"] // 2) // (s["nkv"] // 2)
    lam0 = 0.8 - 0.6 * math.exp(-0.3 * layer)
    lam = 0.0 if fault == "no_lambda" else \
        jnp.exp(jnp.sum(p["lam"][0] * p["lam"][1])) \
        - jnp.exp(jnp.sum(p["lam"][2] * p["lam"][3])) + lam0
    qp = q.reshape(S, s["nh"] // 2, 2, d).transpose(1, 2, 0, 3)
    kp = jnp.repeat(k.reshape(S, s["nkv"] // 2, 2, d), r, 1) \
        .transpose(1, 2, 0, 3)
    vp = jnp.repeat(v.reshape(S, s["nkv"] // 2, 2 * d), r, 1) \
        .transpose(1, 0, 2)

    def pair(args):
        qi, ki, vi = args               # [2, S, d], [2, S, d], [S, 2d]
        both = []
        for half in (0, 1):
            sc = lo(qi[half]) @ lo(ki[half]).T / math.sqrt(d)
            pr = jax.nn.softmax(jnp.where(mask, sc, -1e30), -1)
            both.append(lo(pr) @ lo(vi))
        o = both[0] - lam * both[1]
        o = o / jnp.sqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                         + eps) * p["subln"]
        return o * (1.0 - lam0)

    o = jax.lax.map(pair, (qp, kp, vp))                 # [pairs, S, 2d]
    o = o.transpose(1, 0, 2).reshape(S, -1)
    return lo(o) @ lo(p["w_o"]) + p["b_o"]


@functools.partial(jax.jit, static_argnames=(
    "kind", "layer", "sizes", "eps", "mode", "fault", "chunk"))
def _layer(p, x, memory, k17, v17, kind, layer, sizes, eps, mode, fault,
           chunk):
    """One layer over one sequence ``x`` ``[S, H]`` (the rows after the
    real ones are padding: every mask and the scan are causal, so no real
    row sees them). Returns ``(x, memory, k17, v17)``."""
    s = dict(sizes)
    lo = functools.partial(_lower, mode=mode)
    nh, nkv, d = s["nh"], s["nkv"], s["d"]
    with jax.default_matmul_precision("highest"):
        S = x.shape[0]
        a = _ln(x, p["ln1_w"], p["ln1_b"], eps)
        if kind == "mamba":
            mix, y = _mamba(p, a, s, lo, fault, chunk)
            if layer == s["L"] // 2:
                memory = y
        elif kind == "gmu":
            mix = lo(jax.nn.silu(lo(a) @ lo(p["w_g1"])) * memory) \
                @ lo(p["w_g2"])
        else:
            qkv = lo(a) @ lo(p["w_qkv"]) + p["b_qkv"]
            q = qkv[:, :nh * d].reshape(S, nh, d)
            if kind != "cross":
                k = qkv[:, nh * d:(nh + nkv) * d].reshape(S, nkv, d)
                v = qkv[:, (nh + nkv) * d:].reshape(S, nkv, d)
            if kind == "full":
                k17, v17 = k, v
            if kind == "cross":
                k, v = k17, v17
            pos = jnp.arange(S)
            mask = pos[None] <= pos[:, None]
            if kind == "window" and fault != "window_ignored":
                mask &= pos[None] > pos[:, None] - s["W"]
            if kind == "cross" and fault == "cross_stale":
                # the newest position's row is not in the pages yet
                mask = (pos[None] < pos[:, None]) | (
                    (pos[None] == 0) & (pos[:, None] == 0))
            mix = _diff_attention(p, q, k, v, mask, layer, s, eps, lo,
                                  fault)
        x = x + mix
        a = _ln(x, p["ln2_w"], p["ln2_b"], eps)
        g, u = jnp.split(lo(a) @ lo(p["w1"]), 2, axis=-1)
        x = x + lo(u * jax.nn.silu(g)) @ lo(p["w2"])
        return x, memory, k17, v17


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _head(tables, x, eps, mode):
    with jax.default_matmul_precision("highest"):
        return _lower(_ln(x, tables["lnf_w"], tables["lnf_b"], eps), mode) \
            @ _lower(tables["embed"], mode).T


def _sizes(cfg):
    return tuple(sorted(dims(cfg).items()))


def _hidden(cfg, seed, ids, variants, layers=None):
    """The last layer's output ``[S, H]`` of one padded sequence under
    each of ``variants`` (``(mode, fault)``), a layer of weights made,
    used by every variant and freed before the next. ``layers`` (a list
    of ``(kind, leaves)``) stands in for the seed where the weights fit
    whole (the tests)."""
    eps, chunk = cfg["layer_norm_eps"], cfg["serving"]["prefill_chunk"]
    Di = dims(cfg)["Di"]
    ids = jnp.asarray(ids, jnp.int32)
    embed = table_weights(cfg, seed)["embed"] if layers is None \
        else layers["embed"]
    x = embed[ids]
    del embed
    S = x.shape[0]
    none = jnp.zeros((S, Di), jnp.float32)
    kv = jnp.zeros((S, cfg["num_key_value_heads"],
                    cfg["hidden_size"] // cfg["num_attention_heads"]),
                   jnp.float32)
    state = [(x, none, kv, kv) for _ in variants]
    for l in range(cfg["num_hidden_layers"]):
        kind, p = layer_weights(cfg, seed, l) if layers is None \
            else layers["layers"][l]
        state = [_layer(p, *st, kind, l, _sizes(cfg), eps, mode, fault,
                        chunk) for st, (mode, fault) in zip(state, variants)]
        del p
    return [st[0] for st in state]


def _read(tables, x, rows, eps, mode, tokens=None, pad=512):
    """Of the logits at ``rows`` of ``x``, a block of rows at a time (a
    block of the published vocabulary is 410 MB): the best logit, the
    best token and the logit of each row's ``tokens`` entry, as NumPy
    vectors; only these leave the device."""
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


_PADDED = [0]       # the widest bucket so far: few shapes compile


def served_token_gaps(cfg, weights, prompt, served, pad_to, mode=None,
                      fault=None, layers=None):
    """One forward pass over ``prompt + served``, every layer at every
    position. For each served token, how far its reference logit lies
    below the reference's best at that position. With ``mode`` (the
    control) or ``fault`` (one of ``FAULTS``) the reference so altered
    stands in the program's place: the token that it puts first, at every
    position of the prompt and of the served tokens, is the one held
    against the sound reference.

    ``weights`` is ``init_weights(cfg, seed)``: the seed, from which a
    layer is made when it is needed. The sequence is padded to a bucket
    of its own (1,024s, and never narrower than an earlier call's, so
    that a run's requests share one compiled shape), not to ``pad_to``:
    the model's 262,144 positions are no size to pad to."""
    seed = weights["seed"]
    n, m = len(prompt), len(served)
    S = max(-(-(n + m) // 1024) * 1024, _PADDED[0]) if layers is None \
        else n + m
    _PADDED[0] = S if layers is None else _PADDED[0]
    ids = np.zeros(S, np.int64)
    ids[:n] = prompt
    ids[n:n + m] = served
    stand_in = mode is not None or fault is not None
    variants = [(None, None)] + ([(mode, fault)] if stand_in else [])
    hidden = _hidden(cfg, seed, ids, variants, layers)
    tables = table_weights(cfg, seed) if layers is None \
        else _tables(layers)
    eps = cfg["layer_norm_eps"]
    rows = np.arange(0 if stand_in else n - 1, n + m - 1)
    if stand_in:
        _, tokens, _ = _read(tables, hidden[1], rows, eps, mode)
    else:
        tokens = np.asarray(served, np.int64)
    best, _, held = _read(tables, hidden[0], rows, eps, None, tokens)
    return best - held


def _tables(layers):
    return {k: layers[k] for k in ("embed", "lnf_w", "lnf_b")}


def reference_logits(cfg, layers, ids, mode=None, fault=None):
    """Float32 logits ``[S, V]`` of one sequence from weights that fit
    whole (``layers``: ``{"embed", "lnf_w", "lnf_b", "layers": [(kind,
    leaves)]}``): what the tests hold against the program's reference."""
    (x,) = _hidden(cfg, None, np.asarray(ids), [(mode, fault)], layers)
    return _head(_tables(layers), x, cfg["layer_norm_eps"], mode)


def whole_weights(cfg, seed):
    """Every layer at once (a size that fits): ``reference_logits``'s and
    ``served_token_gaps``'s ``layers``."""
    return dict(table_weights(cfg, seed), layers=[
        layer_weights(cfg, seed, l)
        for l in range(cfg["num_hidden_layers"])])
