"""SDAR-MoE, plainly: the forward pass and the generation loop of
:mod:`.sdar`'s docstring in ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``. No kernel, no cache, no
batching: one sequence, a dense ``b(j) <= b(i)`` mask, the experts as a
loop over all of them with a dense ``[S, E]`` weight that is zero where
an expert was not chosen, and ``generate()`` by repeated full forward
passes. It takes the stacked weights of :func:`.sdar.init_sdar_weights`
and nothing else of the program.

Departures from the published description: none in the mathematics. The
config gives no block length, number of denoising steps, strategy,
threshold or mask id; they are :class:`.sdar.SdarMoeConfig`'s (the
family's ``generate.py``: block 4, 4 steps, ``low_confidence_dynamic``,
0.9, id 151669). Which positions are masked is state, never a comparison
with the mask id, so a prompt may hold that id. ``mode`` rounds every
matmul operand as a lower precision would hold it (``"bf16"``,
``"fp8"``): the control of the tests, never a served path.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["block_mask", "forward", "generate"]


def _lower(x, mode):
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


def block_mask(positions, block):
    """``[S, S]`` bool: row i sees column j iff ``b(j) <= b(i)``."""
    b = np.asarray(positions) // block
    return b[None, :] <= b[:, None]


def _layer(p, experts, x, positions, mask, cfg, mode):
    lo = functools.partial(_lower, mode=mode)
    eps, d, g = cfg.rms_norm_eps, cfg.head_dim, cfg.group
    a = _rms(x, p["ln1"], eps)
    q = jnp.einsum("sh,hkd->skd", lo(a), lo(p["wq"]))
    k = jnp.einsum("sh,hkd->skd", lo(a), lo(p["wk"]))
    v = jnp.einsum("sh,hkd->skd", lo(a), lo(p["wv"]))
    q = _rope(_rms(q, p["q_norm"], eps), positions, cfg.rope_theta)
    k = _rope(_rms(k, p["k_norm"], eps), positions, cfg.rope_theta)
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    scores = jnp.einsum("skd,tkd->kst", lo(q), lo(k)) / math.sqrt(d)
    probs = jax.nn.softmax(jnp.where(mask[None], scores, -1e30), -1)
    o = jnp.einsum("kst,tkd->skd", lo(probs), lo(v))
    x = x + jnp.einsum("skd,kdh->sh", lo(o), lo(p["wo"]))

    a = _rms(x, p["ln2"], eps)
    r = jax.nn.softmax(lo(a) @ lo(p["router"]), -1)
    w, idx = jax.lax.top_k(r, cfg.num_experts_per_tok)
    if cfg.norm_topk_prob:
        w = w / jnp.sum(w, -1, keepdims=True)
    dense = jnp.zeros_like(r).at[jnp.arange(r.shape[0])[:, None], idx].set(w)

    def expert(y, e):
        gate_up, down, w_e = e
        gate, up = jnp.split(lo(a) @ lo(gate_up), 2, axis=-1)
        return y + w_e[:, None] * (lo(jax.nn.silu(gate) * up) @ lo(down)), \
            None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                        (experts["gate_up"], experts["down"], dense.T))
    return x + y


@functools.partial(jax.jit, static_argnames=("cfg", "mode"))
def _forward(weights, ids, positions, mask, cfg, mode):
    E = cfg.num_experts
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][ids]
        for l in range(cfg.num_hidden_layers):
            p = {k: v[l] for k, v in weights["blocks"].items()}
            experts = {k: v[l * E:(l + 1) * E]
                       for k, v in weights["experts"].items()}
            x = _layer(p, experts, x, positions, mask, cfg, mode)
        h = _rms(x, weights["lnf"], cfg.rms_norm_eps)
        return _lower(h, mode) @ _lower(weights["head"], mode)


def forward(cfg, weights, ids, positions=None, mask=None, mode=None):
    """Float32 logits ``[S, V]`` of one sequence ``ids`` ``[S]`` at
    ``positions`` (0..S-1 where not given) under ``mask`` (the block rule
    over those positions where not given)."""
    ids = np.asarray(ids, np.int32).reshape(-1)
    if positions is None:
        positions = np.arange(ids.shape[0])
    if mask is None:
        mask = block_mask(positions, cfg.block_length)
    weights = jax.tree.map(lambda a: a.astype(jnp.float32), weights)
    return _forward(weights, jnp.asarray(ids),
                    jnp.asarray(positions, jnp.int32), jnp.asarray(mask),
                    cfg, mode)


def unmask_choice(conf, masked, threshold, per_pass):
    """Which of one block's masked positions a denoising pass unmasks."""
    conf, masked = np.asarray(conf, np.float64), np.asarray(masked, bool)
    high = masked & (conf > threshold)
    if high.sum() >= per_pass:
        return high
    order = sorted(np.flatnonzero(masked), key=lambda i: (-conf[i], i))
    pick = np.zeros_like(masked)
    pick[order[:per_pass]] = True
    return pick


def generate(cfg, weights, prompt, max_new_tokens, threshold=None,
             mode=None):
    """Greedy generation by diffusion over blocks, every pass a full
    forward over the committed tokens and the block. Returns ``(tokens,
    passes, trace)``: the ``max_new_tokens`` generated ids, for each the
    pass of its block at which it was unmasked, and ``trace`` with one
    entry a pass: ``(block_start, block_tokens_in, masked_in, logits)``,
    the commit passes left out (a full forward has nothing to commit)."""
    threshold = cfg.confidence_threshold if threshold is None else threshold
    bl = cfg.block_length
    prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
    n_full = len(prompt) // bl * bl
    committed, block = prompt[:n_full], prompt[n_full:]
    masked = [False] * len(block) + [True] * (bl - len(block))
    block = block + [cfg.mask_token_id] * (bl - len(block))
    keep = len(prompt) - n_full          # the block's prompt positions
    tokens, passes, trace = [], [], []
    while len(tokens) < max_new_tokens:
        unmasked_at = [-1] * bl
        n_pass = 0
        while any(masked):
            logits = np.asarray(forward(
                cfg, weights, committed + block, mode=mode))[-bl:]
            trace.append((len(committed), list(block), list(masked), logits))
            top = logits.max(-1)
            conf = np.exp(top - (top + np.log(np.exp(
                logits - top[:, None]).sum(-1))))
            pick = unmask_choice(conf, masked, threshold,
                                 cfg.unmask_per_pass)
            for i in np.flatnonzero(pick):
                block[i] = int(logits[i].argmax())
                masked[i] = False
                unmasked_at[i] = n_pass
            n_pass += 1
        tokens += block[keep:]
        passes += unmasked_at[keep:]
        committed, block = committed + block, [cfg.mask_token_id] * bl
        masked, keep = [True] * bl, 0
    return tokens[:max_new_tokens], passes[:max_new_tokens], trace
