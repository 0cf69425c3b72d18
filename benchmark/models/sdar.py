"""The SDAR-MoE family (``model_type`` ``sdar_moe``), as the benchmark
sees it: a Qwen3-MoE-shaped decoder that generates by diffusion over
blocks of tokens. Beside ``gpt.py``, with the same four things:

- the weights, made on the device from the seed **layer by layer** (one
  layer of experts is 1.2 GB in bfloat16 and 2.4 GB in float32): the
  served bfloat16 model is the cast of the reference's float32 draw;
- ``build_engine``: the program's ``SdarServingEngine`` under
  ``ContinuousBatchingScheduler``, from a configuration file;
- the plain reference, the benchmark's own copy (it imports nothing of
  the program; ``tests/test_sdar.py`` holds it equal to the program's
  ``models/sdar_reference.py``): ``jax.numpy`` float32 at
  ``matmul_precision("highest")``, a dense mask, the experts as a loop
  over all of them. At the published widths the float32 model is 18.6
  GiB, so ``served_gaps`` makes, uses and frees one layer at a time over
  every forward pass it has to make;
- the operation counts.

The equations (positions ``p``, block ``b(p) = p // block``): a layer is
``h += Attn(RMS1(h))``, ``h += MoE(RMS2(h))``; ``RMS(v) = v / sqrt(mean
v^2 + eps) * w``; attention has ``num_attention_heads`` query and
``num_key_value_heads`` KV heads of ``head_dim``, q and k each through an
RMSNorm over a head with a learned scale and then RoPE (rotate-half,
absolute positions), query head j reads KV head ``j // group``, **i sees
j iff b(j) <= b(i)**; the MoE takes softmax over all experts, the top k,
renormalised, ``sum w_e W_down,e(silu(W_gate,e a) * W_up,e a)``, and
drops nothing; after the last layer ``RMS_f`` and the untied head.

Departures from the published description: none in the mathematics. What
the config does not give (block length, steps, strategy, threshold, mask
id, the QK-norm's order) is the configuration file's ``assumed``.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

NORMS = ("ln1", "ln2", "q_norm", "k_norm", "lnf")
SERVING_CONTROL = "fp8"
FAULTS = ("stale_commit", "causal_in_block", "drop_last_expert",
          "no_renorm", "wrong_order")


# --------------------------------------------------------------------------
# configuration
# --------------------------------------------------------------------------

def load_config(path):
    with open(path) as f:
        cfg = json.load(f)
    for key in ("hidden_size", "num_hidden_layers", "num_attention_heads",
                "num_key_value_heads", "head_dim", "moe_intermediate_size",
                "num_experts", "num_experts_per_tok", "norm_topk_prob",
                "vocab_size", "rms_norm_eps", "rope_theta",
                "max_position_embeddings", "initializer_range",
                "generation", "serving"):
        if key not in cfg:
            raise ValueError(f"{path}: no {key!r}")
    if cfg["num_attention_heads"] % cfg["num_key_value_heads"]:
        raise ValueError(f"{path}: KV heads do not divide the heads")
    if cfg.get("tie_word_embeddings") or cfg.get("mlp_only_layers") \
            or cfg.get("decoder_sparse_step", 1) != 1:
        raise ValueError(f"{path}: every layer is an expert layer and the "
                         f"head is not tied, in this family")
    return cfg


def block_len(cfg):
    return cfg["generation"]["block_length"]


def weight_shapes(cfg):
    L, H, V = cfg["num_hidden_layers"], cfg["hidden_size"], cfg["vocab_size"]
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    E, F = cfg["num_experts"], cfg["moe_intermediate_size"]
    return {
        "embed": (V, H), "lnf": (H,), "head": (H, V),
        "blocks": {"ln1": (L, H), "wq": (L, H, nh, d), "wk": (L, H, nkv, d),
                   "wv": (L, H, nkv, d), "q_norm": (L, d), "k_norm": (L, d),
                   "wo": (L, nh, d, H), "ln2": (L, H), "router": (L, H, E)},
        "experts": {"gate_up": (L * E, H, 2 * F), "down": (L * E, F, H)},
    }


# --------------------------------------------------------------------------
# operation counts
# --------------------------------------------------------------------------

def active_params(cfg):
    """Parameters one position is multiplied by: attention, the router
    and its k experts in every layer, and the head (709 M at 7 layers)."""
    H, d = cfg["hidden_size"], cfg["head_dim"]
    attn = H * d * 2 * (cfg["num_attention_heads"]
                        + cfg["num_key_value_heads"])
    moe = H * cfg["num_experts"] + cfg["num_experts_per_tok"] * 3 * H \
        * cfg["moe_intermediate_size"]
    return cfg["num_hidden_layers"] * (attn + moe) + H * cfg["vocab_size"]


def serve_flops(cfg, context_lens):
    """Forward FLOPs of processing one position at each of
    ``context_lens``: 2 x the active parameters + 4 L (heads x head_dim)
    x context. Every position of every pass is work done, commit passes
    too: 1.25 passes a token."""
    ctx = np.asarray(context_lens, np.float64)
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    return float(2.0 * active_params(cfg) * ctx.size
                 + 4.0 * cfg["num_hidden_layers"] * width * ctx.sum())


# --------------------------------------------------------------------------
# weights from the seed, a layer at a time
# --------------------------------------------------------------------------

def _key(seed):
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed % (1 << 31)),
                              seed // (1 << 31))


def _draw(key, name, shape, std, dtype):
    w = std * jax.random.normal(key, shape, jnp.float32)
    return (1.0 + w if name in NORMS else w).astype(dtype)


def _leaf_key(seed, name, layer=0):
    names = sorted(("embed", "lnf", "head", "ln1", "wq", "wk", "wv",
                    "q_norm", "k_norm", "wo", "ln2", "router", "gate_up",
                    "down"))
    return jax.random.fold_in(jax.random.fold_in(
        _key(seed), names.index(name)), layer)


@functools.partial(jax.jit, static_argnames=("name", "shape", "std",
                                             "dtype"))
def _make(key, name, shape, std, dtype):
    return _draw(key, name, shape, std, jnp.dtype(dtype))


def _fill(stack, key, layer, name, std, n_layers):
    """Draw one layer's slice of a stacked array into it, in place."""
    part = _draw(key, name, (stack.shape[0] // n_layers,) + stack.shape[1:],
                 std, stack.dtype)
    return jax.lax.dynamic_update_slice_in_dim(
        stack, part, layer * part.shape[0], 0)


def layer_weights(cfg, seed, layer, dtype=jnp.float32):
    """One layer: ``(block leaves without the layer axis, its experts
    [E, ...])``. The same draw as ``init_weights`` makes of that layer."""
    shapes, std = weight_shapes(cfg), cfg["initializer_range"]
    L, dt = cfg["num_hidden_layers"], str(jnp.dtype(dtype))
    block = {k: _make(_leaf_key(seed, k, layer), k, s[1:], std, dt)
             for k, s in shapes["blocks"].items()}
    experts = {k: _make(_leaf_key(seed, k, layer), k,
                        (s[0] // L,) + s[1:], std, dt)
               for k, s in shapes["experts"].items()}
    return block, experts


def table_weights(cfg, seed, dtype=jnp.float32):
    shapes, std = weight_shapes(cfg), cfg["initializer_range"]
    return {k: _make(_leaf_key(seed, k), k, shapes[k], std,
                     str(jnp.dtype(dtype))) for k in ("embed", "lnf", "head")}


def init_weights(cfg, seed, dtype=jnp.float32):
    """Every weight in the program's stacked layout, made on the device
    layer by layer into arrays laid out once (N(0, initializer_range);
    norm scales 1 + N(0, range))."""
    shapes, std = weight_shapes(cfg), cfg["initializer_range"]
    L, dtype = cfg["num_hidden_layers"], jnp.dtype(dtype)
    out = table_weights(cfg, seed, dtype)
    fill = jax.jit(_fill, static_argnames=("name", "std", "n_layers"),
                   donate_argnums=(0,) if jax.default_backend() != "cpu"
                   else ())     # the CPU backend cannot donate
    for group in ("blocks", "experts"):
        out[group] = {}
        for name, shape in shapes[group].items():
            stack = jnp.zeros(shape, dtype)
            for layer in range(L):
                stack = fill(stack, _leaf_key(seed, name, layer),
                             jnp.int32(layer), name, std, L)
            out[group][name] = stack
    return out


# --------------------------------------------------------------------------
# the program's objects
# --------------------------------------------------------------------------

def program_config(cfg):
    from paddle_tpu.models.sdar import SdarMoeConfig
    g = cfg["generation"]
    return SdarMoeConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_experts=cfg["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        norm_topk_prob=cfg["norm_topk_prob"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        max_position_embeddings=cfg["max_position_embeddings"],
        initializer_range=cfg["initializer_range"],
        block_length=g["block_length"],
        denoising_steps=g["denoising_steps"],
        confidence_threshold=g["confidence_threshold"],
        mask_token_id=g["mask_token_id"])


def build_engine(cfg, deploy, seed):
    """``SdarServingEngine`` as the configuration's ``serving`` group
    deploys it, the weights made from the seed in the served type."""
    from paddle_tpu.serving import SdarServingEngine
    s = cfg["serving"]
    if s["weight_dtype"] != s["kv_dtype"] or s["sampling"] != "greedy":
        raise ValueError("one served type, greedy")
    return SdarServingEngine(
        init_weights(cfg, seed, dtype=s["weight_dtype"]),
        program_config(cfg), page_size=s["page_size"],
        num_pages=deploy["pool_tokens"] // s["page_size"] + 1,
        max_seq_len=deploy["max_seq_len"],
        decode_buckets=tuple(deploy["decode_buckets"]),
        prefill_chunk=s["prefill_chunk"], prefix_cache=s["prefix_cache"])


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
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


def _rope(x, positions, theta):
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rotated * sin


@functools.partial(jax.jit, static_argnames=("dims", "mode", "fault"))
def _layer(p, experts, x, positions, mask, dims, mode, fault):
    """One layer over one sequence ``x`` ``[S, H]`` under a dense mask."""
    eps, theta, top_k, renorm = dims
    lo = functools.partial(_lower, mode=mode)
    with jax.default_matmul_precision("highest"):
        d, g = p["wq"].shape[-1], p["wq"].shape[1] // p["wk"].shape[1]
        a = _rms(x, p["ln1"], eps)
        q = jnp.einsum("sh,hkd->skd", lo(a), lo(p["wq"]))
        k = jnp.einsum("sh,hkd->skd", lo(a), lo(p["wk"]))
        v = jnp.einsum("sh,hkd->skd", lo(a), lo(p["wv"]))
        q = _rope(_rms(q, p["q_norm"], eps), positions, theta)
        k = _rope(_rms(k, p["k_norm"], eps), positions, theta)
        k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
        scores = jnp.einsum("skd,tkd->kst", lo(q), lo(k)) / math.sqrt(d)
        probs = jax.nn.softmax(jnp.where(mask[None], scores, -1e30), -1)
        o = jnp.einsum("kst,tkd->skd", lo(probs), lo(v))
        x = x + jnp.einsum("skd,kdh->sh", lo(o), lo(p["wo"]))

        a = _rms(x, p["ln2"], eps)
        r = jax.nn.softmax(lo(a) @ lo(p["router"]), -1)
        w, idx = jax.lax.top_k(r, top_k)
        if fault == "drop_last_expert":
            w, idx = w[:, :-1], idx[:, :-1]
        if renorm and fault != "no_renorm":
            w = w / jnp.sum(w, -1, keepdims=True)
        dense = jnp.zeros_like(r).at[
            jnp.arange(r.shape[0])[:, None], idx].set(w)

        def expert(y, e):
            gate_up, down, w_e = e
            gate, up = jnp.split(lo(a) @ lo(gate_up), 2, axis=-1)
            return y + w_e[:, None] * (
                lo(jax.nn.silu(gate) * up) @ lo(down)), None

        y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                            (experts["gate_up"], experts["down"], dense.T))
        return x + y


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _head(tables, x, eps, mode):
    with jax.default_matmul_precision("highest"):
        return _lower(_rms(x, tables["lnf"], eps), mode) \
            @ _lower(tables["head"], mode)


def _read(tables, x, rows, tokens, eps, mode, pad=512):
    """Of the logits at ``rows`` of ``x``: the logit of each row's token,
    the best logit, the log of the sum of exponentials and the best
    token, as NumPy vectors. Rows are padded to a multiple of ``pad`` so
    that few shapes compile, and only these vectors leave the device."""
    n = len(rows)
    fill = -(-n // pad) * pad - n
    rows = jnp.asarray(np.concatenate([rows, np.zeros(fill, np.int64)]),
                       jnp.int32)
    tokens = jnp.asarray(np.concatenate([tokens, np.zeros(fill, np.int64)]),
                         jnp.int32)
    logits = _head(tables, x[rows], eps, mode)
    out = (jnp.take_along_axis(logits, tokens[:, None], 1)[:, 0],
           logits.max(-1), jax.nn.logsumexp(logits, -1), logits.argmax(-1))
    return tuple(np.asarray(v)[:n] for v in out)


def _dims(cfg):
    return (cfg["rms_norm_eps"], float(cfg["rope_theta"]),
            cfg["num_experts_per_tok"], bool(cfg["norm_topk_prob"]))


def reference_logits(cfg, weights, ids, positions, mask, mode=None,
                     fault=None):
    """Float32 logits ``[S, V]`` of one sequence under a dense mask, from
    weights in the stacked layout (a size that fits whole)."""
    E = cfg["num_experts"]
    w = jax.tree.map(lambda a: a.astype(jnp.float32), weights)
    x = w["embed"][jnp.asarray(ids, jnp.int32)]
    positions, mask = jnp.asarray(positions, jnp.int32), jnp.asarray(mask)
    for l in range(cfg["num_hidden_layers"]):
        p = {k: v[l] for k, v in w["blocks"].items()}
        experts = {k: v[l * E:(l + 1) * E] for k, v in w["experts"].items()}
        x = _layer(p, experts, x, positions, mask, _dims(cfg), mode, fault)
    return _head(w, x, cfg["rms_norm_eps"], mode)


# ---- what the engine served, pass by pass ----------------------------------

class Replay:
    """One served request laid out for the reference. ``record`` is the
    engine's: ``(token, pass, confidence)`` of every generated position,
    those past ``max_new_tokens`` too (the confidence: the softmax
    probability the program read for the token at that pass). Positions
    0..T-1 are the prompt and the generated blocks; ``first`` is where
    the first generated block starts. One forward pass serves all blocks' states at one pass index
    ``s``: the sequence is ``[clean | noisy]``, the clean half the final
    tokens under the block rule, the noisy half the generated blocks as
    they stood before pass ``s`` (a position unmasked at an earlier pass
    holds its token, the others the mask id), each noisy block seeing
    the clean blocks before it and itself: the block-diffusion training
    mask."""

    def __init__(self, cfg, prompt, record, pad=512):
        bl = block_len(cfg)
        self.bl, self.mask_id = bl, cfg["generation"]["mask_token_id"]
        prompt = [int(t) for t in prompt]
        self.first = len(prompt) // bl * bl
        keep = len(prompt) - self.first
        self.final = np.asarray(prompt + [int(t) for t, _, _ in record],
                                np.int64)
        self.at_pass = np.asarray(
            [-1] * len(prompt) + [int(s) for _, s, _ in record], np.int64)
        self.conf = np.asarray(
            [1.0] * len(prompt) + [float(c) for _, _, c in record])
        self.T = T = len(self.final)
        if T % bl or keep + len(record) != T - self.first:
            raise ValueError("the record does not fill whole blocks")
        G = T - self.first
        self.S = -(-(T + G) // pad) * pad
        pos = np.concatenate([np.arange(T), np.arange(self.first, T)])
        self.positions = np.concatenate(
            [pos, np.zeros(self.S - T - G, np.int64)])
        self.n_pass = int(self.at_pass.max()) + 1

    def mask(self, fault=None):
        T, S, bl = self.T, self.S, self.bl
        b = self.positions[:2 * T - self.first] // bl
        clean = np.arange(len(b)) < T
        see = np.where(
            clean[:, None] & clean[None, :], b[None, :] <= b[:, None],
            np.where(~clean[:, None] & clean[None, :],
                     b[None, :] < b[:, None],
                     np.where(~clean[:, None] & ~clean[None, :],
                              b[None, :] == b[:, None], False)))
        if fault == "causal_in_block":
            p = self.positions[:len(b)]
            see &= (b[None, :] != b[:, None]) | (p[None, :] <= p[:, None])
        out = np.eye(S, dtype=bool)         # padding sees itself alone
        out[:len(b), :len(b)] = see
        return out

    def ids(self, s, fault=None):
        """The sequence before pass ``s``."""
        clean = self.final.copy()
        if fault == "stale_commit":
            # the rows a commit stored are those of the block's last
            # denoising pass: what that pass unmasked is still the mask id
            gen = self.at_pass >= 0
            for lo in range(self.first, self.T, self.bl):
                blk = slice(lo, lo + self.bl)
                last = self.at_pass[blk].max()
                clean[blk] = np.where(gen[blk] & (self.at_pass[blk] == last),
                                      self.mask_id, clean[blk])
        noisy = np.where((self.at_pass >= 0) & (self.at_pass >= s),
                         self.mask_id, self.final)[self.first:]
        return np.concatenate([clean, noisy, np.zeros(
            self.S - self.T - len(noisy), np.int64)])

    def masked_at(self, s):
        """Generated positions still masked before pass ``s``, as indices
        into the noisy half; and which of them pass ``s`` unmasked."""
        at = self.at_pass[self.first:]
        return np.flatnonzero(at >= s), np.flatnonzero(at == s)


def served_gaps(cfg, seed, served, mode=None, fault=None):
    """Hold what the engine served against the reference, pass by pass.

    ``served`` is ``[(prompt, record)]``. For every pass index the
    reference recomputes every block's logits from the prompt, the
    served tokens and the record. ``logit_gap``: the widest gap by which
    a served token's reference logit lies below the reference's best at
    its position and pass. ``order_gap``: the mean, over the passes that
    had a choice, of the gap in log-confidence by which the position the
    engine unmasked lies below the most confident masked position of its
    block that it left (0 where it took the most confident; the widest
    such gap reads 0.04 to 0.09 on sound runs and 0.13 to 0.19 under the
    wrong order: no limit has room between, the mean has ten times).
    ``conf_gap``: the mean distance between the log of the confidence
    the program read for a token at the pass that unmasked it and the
    reference's log-probability of that token there: numbers compared,
    where the other two compare choices, so a fault that shifts every
    logit a little (a dropped expert) is told from rounding. With
    ``mode`` (the control) or ``fault`` (one of ``FAULTS``: a commit that
    stored a denoising pass's rows, a causal mask inside the block, the
    last expert dropped, weights not renormalised, the least confident
    position unmasked) the reference so altered stands in the program's
    place: at every pass the token it puts first, its confidence in it
    and the position it would unmask are the ones held against the sound
    reference. Layers are made from the seed one at a time, and every
    forward pass goes through a layer before the next layer is made."""
    replays = [Replay(cfg, p, rec) for p, rec in served]
    stand_in = mode is not None or fault is not None
    jobs = []           # [replay, pass, x, x of the stand-in]
    tables = table_weights(cfg, seed)
    for r in replays:
        for s in range(r.n_pass):
            x = tables["embed"][jnp.asarray(r.ids(s), jnp.int32)]
            alt = tables["embed"][jnp.asarray(r.ids(s, fault), jnp.int32)] \
                if stand_in else None
            jobs.append([r, s, x, alt])
    masks = {id(r): (jnp.asarray(r.mask()),
                     jnp.asarray(r.mask(fault)) if stand_in else None)
             for r in replays}
    for l in range(cfg["num_hidden_layers"]):
        p, experts = layer_weights(cfg, seed, l)
        for job in jobs:
            r = job[0]
            pos = jnp.asarray(r.positions, jnp.int32)
            job[2] = _layer(p, experts, job[2], pos, masks[id(r)][0],
                            _dims(cfg), None, None)
            if stand_in:
                job[3] = _layer(p, experts, job[3], pos, masks[id(r)][1],
                                _dims(cfg), mode, fault)
        del p, experts
    logit_gap = apart = behind = 0.0
    checked = choices = 0
    for r, s, x, alt in jobs:
        masked, picked = r.masked_at(s)
        rows = r.T + masked
        tokens = r.final[r.first + masked]
        choice = np.isin(masked, picked)
        if stand_in:
            _, top, lse, tokens = _read(tables, alt, rows, tokens,
                                        cfg["rms_norm_eps"], mode)
            theirs = top - lse
        else:           # read only where the pass unmasked the position
            theirs = np.log(np.maximum(r.conf[r.first + masked], 1e-30))
        held, best, lse, _ = _read(tables, x, rows, tokens,
                                   cfg["rms_norm_eps"], None)
        conf = best - lse
        block = masked // r.bl
        for b in np.unique(block):
            mine = block == b
            if stand_in:        # the position the stand-in would unmask
                took = np.zeros(mine.sum(), bool)
                took[(np.argmin if fault == "wrong_order" else np.argmax)(
                    theirs[mine])] = True
            else:
                took = choice[mine]
            if took.any():
                gap = best[mine][took] - held[mine][took]
                logit_gap = max(logit_gap, float(gap.max()))
                apart += float(np.abs(
                    theirs[mine][took] - (held - lse)[mine][took]).sum())
                checked += int(took.sum())
                if (~took).any():
                    behind += max(0.0, float(
                        conf[mine][~took].max() - conf[mine][took].min()))
                    choices += 1
    return {"logit_gap": logit_gap,
            "order_gap": behind / max(choices, 1),
            "conf_gap": apart / max(checked, 1),
            "checked_tokens": checked}
