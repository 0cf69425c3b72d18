"""The GPT decoder family, as the benchmark sees it.

Four things live here and nowhere else, so that another family (an MoE
block, a modern block) arrives as another file beside this one:

- ``init_weights``: the weights, made on the device from the seed in one
  jitted call. The program is handed them; the reference makes them again.
- ``build_train_step`` / ``build_engine``: the program's own entry objects
  (``GPTHybridTrainStep``; ``ServingEngine`` under
  ``ContinuousBatchingScheduler``), built from a configuration file.
- the plain reference: the published pre-LN decoder (learned positions,
  LayerNorm, tanh-GELU, tied head) in ``jax.numpy`` float32 at
  ``matmul_precision("highest")``: forward, loss, gradients, AdamW. It
  imports nothing of the program and takes nothing the program made.
- the operation counts: matmul parameters and FLOPs per token.

Departures of the reference from the published description: none in the
mathematics. Dropout is 0 (the program has none); the causal mask is
``-1e30`` before a float32 softmax.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

BLOCK_KEYS = ("ln1_w", "ln1_b", "wqkv", "bqkv", "wo", "bo",
              "ln2_w", "ln2_b", "w1", "b1", "w2", "b2")
DECAYED = ("wqkv", "wo", "w1", "w2", "wte", "wpe")


# --------------------------------------------------------------------------
# configuration
# --------------------------------------------------------------------------

def load_config(path):
    with open(path) as f:
        cfg = json.load(f)
    for key in ("hidden_size", "num_layers", "num_heads",
                "intermediate_size", "vocab_size",
                "max_position_embeddings", "layer_norm_epsilon",
                "initializer_range"):
        if key not in cfg:
            raise ValueError(f"{path}: no {key!r}")
    if cfg["hidden_size"] % cfg["num_heads"]:
        raise ValueError(f"{path}: heads do not divide the hidden size")
    return cfg


def head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_heads"]


def weight_shapes(cfg):
    H, nh, d = cfg["hidden_size"], cfg["num_heads"], head_dim(cfg)
    Fm, L, V = cfg["intermediate_size"], cfg["num_layers"], cfg["vocab_size"]
    return {
        "blocks": {
            "ln1_w": (L, H), "ln1_b": (L, H),
            "wqkv": (L, H, 3, nh, d), "bqkv": (L, 3, nh, d),
            "wo": (L, nh, d, H), "bo": (L, H),
            "ln2_w": (L, H), "ln2_b": (L, H),
            "w1": (L, H, Fm), "b1": (L, Fm),
            "w2": (L, Fm, H), "b2": (L, H),
        },
        "wte": (V, H),
        "wpe": (cfg["max_position_embeddings"], H),
        "lnf_w": (H,), "lnf_b": (H,),
    }


# --------------------------------------------------------------------------
# operation counts
# --------------------------------------------------------------------------

def matmul_params(cfg):
    """Parameters that a token is multiplied by: qkv, out, the two MLP
    matrices of every layer, and the tied head. The position table is a
    lookup, not a matmul, and is not counted (the program's own
    ``model_flops_per_token`` counts it: 0.3% at 345M)."""
    H, L = cfg["hidden_size"], cfg["num_layers"]
    per_layer = 4 * H * H + 2 * H * cfg["intermediate_size"]
    return cfg["vocab_size"] * H + L * per_layer


def train_flops_per_token(cfg, seq_len):
    """Forward and backward, no recomputation: 6 N for the matmuls and
    12 L H S for the two attention products (the usual full-square
    convention, as Megatron-LM and the program's own count take it)."""
    return 6 * matmul_params(cfg) \
        + 12 * cfg["num_layers"] * cfg["hidden_size"] * seq_len


def serve_flops(cfg, context_lens):
    """Forward FLOPs of processing one token at each of ``context_lens``
    (the number of positions it attends over): 2 N + 4 L H context."""
    ctx = np.asarray(context_lens, np.float64)
    return float(2.0 * matmul_params(cfg) * ctx.size
                 + 4.0 * cfg["num_layers"] * cfg["hidden_size"] * ctx.sum())


# --------------------------------------------------------------------------
# weights from the seed
# --------------------------------------------------------------------------

def _key(seed):
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed % (1 << 31)),
                              seed // (1 << 31))


def _init(key, cfg, dtype):
    std = cfg["initializer_range"]
    res_std = std / math.sqrt(2.0 * cfg["num_layers"])
    shapes = weight_shapes(cfg)
    flat = {("blocks", k): s for k, s in shapes["blocks"].items()}
    flat.update({(k,): s for k, s in shapes.items() if k != "blocks"})
    out = {"blocks": {}}
    for i, (path, shape) in enumerate(sorted(flat.items())):
        name = path[-1]
        scale = res_std if name in ("wo", "w2") else std
        w = scale * jax.random.normal(jax.random.fold_in(key, i), shape,
                                      jnp.float32)
        if name in ("ln1_w", "ln2_w", "lnf_w"):
            w = 1.0 + w
        w = w.astype(dtype)
        if len(path) == 2:
            out["blocks"][name] = w
        else:
            out[name] = w
    return out


def init_weights(cfg, seed, dtype=jnp.float32, shardings=None):
    """Every weight, made on the device in one jitted call. Matrices are
    N(0, initializer_range) with GPT-2's scaled residual projections;
    LayerNorm scales are 1 + N(0, range) and biases N(0, range), as after
    some training, so that no term of the block is multiplied by zero.
    ``dtype`` rounds the same float32 draw, so the served bfloat16 model
    is the cast of the reference's float32 one."""
    make = jax.jit(lambda key: _init(key, cfg, jnp.dtype(dtype)),
                   out_shardings=shardings)
    return make(_key(seed))


# --------------------------------------------------------------------------
# the program's objects
# --------------------------------------------------------------------------

def program_config(cfg):
    from paddle_tpu.models.gpt import GPTConfig
    return GPTConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_layers"], num_heads=cfg["num_heads"],
        intermediate_size=cfg["intermediate_size"],
        max_position_embeddings=cfg["max_position_embeddings"],
        layer_norm_epsilon=cfg["layer_norm_epsilon"],
        initializer_range=cfg["initializer_range"])


class _Holder:
    pass


class _Value:
    def __init__(self, value):
        self._value = value


class _HandOver:
    """The stacked weights, handed to ``stack_gpt_weights`` layer by layer
    as the eager model would hold them, each stacked array let go once its
    last layer has been read: the engine stacks them again, and two whole
    copies beside a pool sized to the chip would not fit."""

    def __init__(self, blocks, layers):
        self._blocks = dict(blocks)
        self._left = {k: layers for k in blocks}

    def take(self, key, layer):
        value = self._blocks[key][layer]
        self._left[key] -= 1
        if not self._left[key]:
            del self._blocks[key]
        return value


class _Layer:
    def __init__(self, hand, index):
        self._hand, self._index = hand, index

    def __getattr__(self, key):
        if key not in BLOCK_KEYS:
            raise AttributeError(key)
        return _Value(self._hand.take(key, self._index))


def as_program_model(cfg, weights):
    """The eager model's shape, as far as ``stack_gpt_weights`` reads it.
    The eager build itself (40 s at 1.3B on the host) is what this stands
    in for. ``weights`` is emptied."""
    hand = _HandOver(weights.pop("blocks"), cfg["num_layers"])
    gpt = _Holder()
    gpt.config = program_config(cfg)
    gpt.layers = [_Layer(hand, i) for i in range(cfg["num_layers"])]
    gpt.embeddings = _Holder()
    gpt.embeddings.word_embeddings = _Value(weights.pop("wte"))
    gpt.embeddings.position_embeddings = _Value(weights.pop("wpe"))
    gpt.lnf_w = _Value(weights.pop("lnf_w"))
    gpt.lnf_b = _Value(weights.pop("lnf_b"))
    return gpt


def adamw_hyper(cfg):
    t = cfg["training"]
    return dict(lr=t["lr"], beta1=t["beta1"], beta2=t["beta2"],
                eps=t["adam_eps"], weight_decay=t["weight_decay"],
                grad_clip_norm=t["grad_clip_norm"])


def build_train_step(cfg, job, seed, lower_precision=False):
    """``GPTHybridTrainStep`` on the job's mesh. Its own constructor
    stacks an eager model's layers (three copies of the weights in flight,
    and the host build before them); here its compile-only constructor
    lays the step out and the state is made in place from the seed:
    float32 masters in one jitted call, already sharded, and zero
    moments. ``lower_precision`` switches on the program's own bfloat16
    masters and moments: the control, never a cell."""
    from paddle_tpu.distributed.mesh import HybridCommunicateGroup
    from paddle_tpu.models.gpt import GPTHybridTrainStep
    t = cfg["training"]
    if t["master_dtype"] != "float32" or t["moment_dtype"] != "float32":
        raise ValueError("the configuration must state float32 masters")
    hcg = HybridCommunicateGroup(dp_degree=job["dp"], mp_degree=job["mp"],
                                 pp_degree=job["pp"])
    kw = dict(param_dtype="bfloat16", moment_dtype="bfloat16") \
        if lower_precision else {}
    step = GPTHybridTrainStep.abstract(
        program_config(cfg), hcg, n_micro=job["n_micro"],
        remat=job["remat"], compute_dtype=t["compute_dtype"],
        **adamw_hyper(cfg), **kw)
    named = lambda specs: jax.tree.map(
        lambda s: NamedSharding(step.mesh, s), specs,
        is_leaf=lambda s: isinstance(s, P))
    want = jax.tree.map(lambda a: (a.shape, a.dtype), step.params)
    step.params = init_weights(cfg, seed, dtype=step.param_dtype
                               or jnp.float32,
                               shardings=named(step.param_specs))
    if jax.tree.map(lambda a: (a.shape, a.dtype), step.params) != want:
        raise RuntimeError("the step's parameter layout is not the "
                           "benchmark's")
    zeros = jax.jit(
        lambda: jax.tree.map(
            lambda a: jnp.zeros(a.shape, step.moment_dtype), step.params),
        out_shardings=named(step.state_specs))
    step.opt_state = {"m": zeros(), "v": zeros()}
    return step


def build_engine(cfg, deploy, seed):
    """``ServingEngine`` as the configuration's ``serving`` group deploys
    it, the weights made from the seed in the served type."""
    from paddle_tpu.serving import ServingEngine
    s = cfg["serving"]
    weights = init_weights(cfg, seed, dtype=s["weight_dtype"])
    return ServingEngine(
        as_program_model(cfg, weights), program_config(cfg),
        page_size=s["page_size"],
        num_pages=deploy["pool_tokens"] // s["page_size"] + 1,
        max_seq_len=cfg["max_position_embeddings"],
        decode_buckets=tuple(deploy["decode_buckets"]),
        prefill_chunk=s["prefill_chunk"], prefix_cache=s["prefix_cache"],
        temperature=0.0)


# --------------------------------------------------------------------------
# the plain reference
# --------------------------------------------------------------------------

# The serving control: the nearest precision below the served bfloat16
# that is lower in effect. The program's own weight-only int8 is not: a
# scale per channel keeps a weight's eight significant bits (PERF.md).
SERVING_CONTROL = "fp8"


def _lower(x, mode):
    """Round a matmul operand as the control's precision would hold it."""
    if mode is None:
        return x
    if mode == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if mode == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    raise ValueError(mode)


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(p, x, eps, mode=None, fault=None):
    lo = lambda a: _lower(a, mode)
    S = x.shape[1]
    d = p["wqkv"].shape[-1]
    h = _layer_norm(x, p["ln1_w"], p["ln1_b"], eps)
    qkv = jnp.einsum("bsh,hknd->bsknd", lo(h), lo(p["wqkv"])) + p["bqkv"]
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    scores = jnp.einsum("bsnd,btnd->bnst", lo(q), lo(k)) / math.sqrt(d)
    causal = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -1e30), -1)
    attn = jnp.einsum("bnst,btnd->bsnd", lo(probs), lo(v))
    wo, w2 = p["wo"], p["w2"]
    if fault == "no_mp_exchange":
        # what a rank of mp2 holds when the sum over ranks is left out:
        # the row-parallel products of its own half of heads and of F
        attn, wo = attn[:, :, :attn.shape[2] // 2], wo[:wo.shape[0] // 2]
    x = x + jnp.einsum("bsnd,ndh->bsh", lo(attn), lo(wo)) + p["bo"]
    h = _layer_norm(x, p["ln2_w"], p["ln2_b"], eps)
    u = _gelu_tanh(jnp.einsum("bsh,hf->bsf", lo(h), lo(p["w1"])) + p["b1"])
    if fault == "no_mp_exchange":
        u, w2 = u[..., :u.shape[-1] // 2], w2[:w2.shape[0] // 2]
    return x + jnp.einsum("bsf,fh->bsh", lo(u), lo(w2)) + p["b2"]


def _hidden(w, ids, eps, mode=None, fault=None, remat=False):
    x = w["wte"][ids] + w["wpe"][jnp.arange(ids.shape[1])]
    blk = lambda p, xx: _block(p, xx, eps, mode, fault)
    if remat:
        blk = jax.checkpoint(blk)
    x, _ = jax.lax.scan(lambda xx, p: (blk(p, xx), None), x, w["blocks"])
    return _layer_norm(x, w["lnf_w"], w["lnf_b"], eps)


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _logits_jit(w, ids, eps, mode):
    with jax.default_matmul_precision("highest"):
        h = _hidden(w, ids, eps, mode)
        return jnp.einsum("bsh,vh->bsv", _lower(h, mode),
                          _lower(w["wte"], mode))


def reference_logits(cfg, weights, ids, mode=None):
    """Float32 logits ``[B, S, V]`` of a full causal forward pass."""
    return _logits_jit(weights, jnp.asarray(ids, jnp.int32),
                       cfg["layer_norm_epsilon"], mode)


def served_token_gaps(cfg, weights, prompt, served, pad_to, mode=None):
    """One forward pass over ``prompt + served``. For each served token,
    how far its reference logit lies below the reference's best at that
    position. With ``mode`` (the control: it need not decode), the same
    for the token that the lower precision puts first, at every position
    of the prompt and of the served tokens."""
    n, m = len(prompt), len(served)
    ids = np.zeros((1, pad_to), np.int32)
    ids[0, :n] = prompt
    ids[0, n:n + m] = served
    at = slice(n - 1, n + m - 1) if mode is None else slice(0, n + m - 1)
    ref = reference_logits(cfg, weights, ids)[0, at]
    best = ref.max(-1)
    if mode is None:
        tok = jnp.asarray(served, jnp.int32)
    else:
        tok = jnp.argmax(reference_logits(cfg, weights, ids, mode)[0, at],
                         -1)
    return np.asarray(best - jnp.take_along_axis(ref, tok[:, None], 1)[:, 0])


# ---- training: loss, gradients, AdamW -------------------------------------

def reference_shardings(cfg, devices):
    """The reference is one plain program; over several chips its weights
    are laid out by heads, by the MLP's width and by vocabulary rows, and
    XLA partitions the arithmetic. On one chip this is no sharding."""
    mesh = Mesh(np.asarray(devices), ("x",))
    n = len(devices)
    if cfg["num_heads"] % n or cfg["intermediate_size"] % n \
            or cfg["vocab_size"] % n:
        raise ValueError(f"{n} chips do not divide the reference's widths")
    spec = {"blocks": {k: P() for k in BLOCK_KEYS}, "wte": P("x", None),
            "wpe": P(), "lnf_w": P(), "lnf_b": P()}
    spec["blocks"].update(
        wqkv=P(None, None, None, "x", None), bqkv=P(None, None, "x", None),
        wo=P(None, "x", None, None), w1=P(None, None, "x"),
        b1=P(None, "x"), w2=P(None, "x", None))
    tree = jax.tree.map(lambda s: NamedSharding(mesh, s), spec,
                        is_leaf=lambda s: isinstance(s, P))
    return tree, NamedSharding(mesh, P())


def _loss_sum(w, ids, labels, eps, fault):
    h = _hidden(w, ids, eps, fault=fault, remat=True)
    logits = jnp.einsum("bsh,vh->bsv", h, w["wte"])
    lse = jax.scipy.special.logsumexp(logits, -1)
    tgt = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.sum(lse - tgt)


@functools.partial(jax.jit, static_argnames=("eps", "fault"))
def _grad_rows(w, ids, labels, eps, fault):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(_loss_sum)(w, ids, labels, eps, fault)


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _accumulate(acc, g, loss_acc, loss):
    return jax.tree.map(jnp.add, acc, g), loss_acc + loss


@functools.partial(jax.jit, donate_argnums=0)
def _mean(acc, n_tokens):
    return jax.tree.map(lambda x: x / n_tokens, acc)


def split_leaves(tree):
    """The leaves that norms are taken over: every stacked array by
    layer, ``wqkv``/``bqkv`` by query, key and value as well (a key's
    bias has no gradient under softmax, the other two have)."""
    out = {}
    for k, v in tree["blocks"].items():
        if k in ("wqkv", "bqkv"):
            axis = 2 if k == "wqkv" else 1
            for j, part in enumerate("qkv"):
                out[f"{k}.{part}"] = jnp.take(v, j, axis=axis)
        else:
            out[k] = v
    for k in ("wte", "wpe", "lnf_w", "lnf_b"):
        out[k] = tree[k][None]
    return out


@jax.jit
def leaf_norms(tree, scale=1.0):
    """``{leaf: [layers]}`` of L2 norms, float32."""
    return {k: scale * jnp.sqrt(jnp.sum(
        jnp.square(v.astype(jnp.float32)).reshape(v.shape[0], -1), -1))
        for k, v in split_leaves(tree).items()}


@jax.jit
def change_norms(tree, start):
    return leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        tree, start))


@functools.partial(jax.jit, static_argnames=("hyper",), donate_argnums=(0, 2, 3))
def _adamw(w, g, m, v, t, hyper):
    lr, b1, b2, eps, wd, clip = hyper
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                         for x in jax.tree.leaves(g)))
    scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-6))

    def upd(path, p, gg, mm, vv):
        gg = gg * scale
        m2 = b1 * mm + (1 - b1) * gg
        v2 = b2 * vv + (1 - b2) * jnp.square(gg)
        step = (m2 / (1 - b1 ** t)) / (jnp.sqrt(v2 / (1 - b2 ** t)) + eps)
        decay = wd if path[-1].key in DECAYED else 0.0
        return p * (1 - lr * decay) - lr * step, m2, v2

    out = jax.tree_util.tree_map_with_path(upd, w, g, m, v)
    pick = lambda i: jax.tree.map(lambda o: o[i], out,
                                  is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2), scale


def reference_train(cfg, seed, batches, rows_per_pass, devices,
                    fault=None):
    """Follow the program's first ``len(batches)`` steps in float32:
    the same weights from the seed, the same batches, mean cross-entropy
    over all rows, clipping by the global norm, AdamW with decay on the
    matrices and embeddings only. Rows go through in passes of
    ``rows_per_pass`` so that the activations fit beside the state.

    ``fault`` plants what a broken step would compute, for the readings
    that a limit is held against: ``half_batch`` (the second half of the
    rows left out, the mean taken over the rest) and ``no_mp_exchange``.

    Returns ``{"loss": [per step], "grad": {leaf: norms} of the first
    step's gradient as the optimizer gets it, "change": {leaf: norms} of
    the parameters' change over all the steps}``.
    """
    t = cfg["training"]
    hyper = (t["lr"], t["beta1"], t["beta2"], t["adam_eps"],
             t["weight_decay"], t["grad_clip_norm"])
    eps = cfg["layer_norm_epsilon"]
    w_sh, rep = reference_shardings(cfg, devices)
    w = init_weights(cfg, seed, shardings=w_sh)
    zeros = jax.jit(lambda tree: jax.tree.map(jnp.zeros_like, tree),
                    out_shardings=w_sh)
    m, v = zeros(w), zeros(w)
    losses, grad = [], None
    for step_no, (ids, labels) in enumerate(batches, start=1):
        if fault == "half_batch":
            ids, labels = ids[:len(ids) // 2], labels[:len(labels) // 2]
        acc, loss = zeros(w), jnp.zeros((), jnp.float32)
        for r in range(0, len(ids), rows_per_pass):
            put = lambda a: jax.device_put(
                jnp.asarray(a[r:r + rows_per_pass], jnp.int32), rep)
            l, g = _grad_rows(w, put(ids), put(labels), eps, fault)
            acc, loss = _accumulate(acc, g, loss, l)
        n_tok = ids.shape[0] * ids.shape[1]
        g = _mean(acc, float(n_tok))
        losses.append(float(loss) / n_tok)
        if step_no == 1:
            gnorms = leaf_norms(g)
        w, m, v, scale = _adamw(w, g, m, v, float(step_no), hyper)
        if step_no == 1:
            grad = {k: np.asarray(n) * float(scale)
                    for k, n in gnorms.items()}
    start = init_weights(cfg, seed, shardings=w_sh)
    change = {k: np.asarray(n) for k, n in change_norms(w, start).items()}
    return {"loss": losses, "grad": grad, "change": change}
