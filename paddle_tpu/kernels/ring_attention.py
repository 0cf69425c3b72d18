"""Ring attention: exact attention over sequences sharded on the ``sep`` axis.

Capability gap filled: the reference has NO sequence/context parallelism
(SURVEY.md §2.4 — grep-verified absent); its only long-sequence levers are
recompute + TP head sharding. This module provides the TPU-native long-context
path: each device holds a sequence block of Q/K/V, K/V blocks rotate around
the ring via ``lax.ppermute`` (ICI neighbor hops — bandwidth-optimal), and the
per-block partial attention is merged with the online-softmax
(log-sum-exp carry) used by flash attention, so the result is EXACT attention
over the full sequence while no device ever materializes more than
[B, H, S_local, S_local] logits.

Memory: per-step remat (``jax.checkpoint`` on the scan body) keeps backward
memory at one block of residuals; communication overlaps compute because each
step's ppermute is independent of that step's matmuls (XLA's latency-hiding
scheduler pipelines the ring).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

_MASKED = -1e30


def ring_attention_local(q, k, v, axis_name: str, causal: bool = False,
                         scale: float | None = None):
    """Run inside shard_map: q/k/v are LOCAL blocks [B, S_loc, H, D] of a
    sequence sharded over `axis_name`; returns the local output block.
    """
    B, Sl, H, D = q.shape
    s = scale if scale is not None else 1.0 / math.sqrt(D)
    from .._jax_compat import axis_size as _axis_size
    n = _axis_size(axis_name)
    me = lax.axis_index(axis_name)

    qt = jnp.einsum("bshd->bhsd", q).astype(jnp.float32)
    kt = jnp.einsum("bshd->bhsd", k)
    vt = jnp.einsum("bshd->bhsd", v)

    q_pos = me * Sl + lax.broadcasted_iota(jnp.int32, (Sl, Sl), 0)

    perm = [(j, (j + 1) % n) for j in range(n)]

    def step(carry, i):
        o, m, l, kb, vb = carry
        src = (me - i) % n  # ring position the current kv block came from
        logits = jnp.einsum("bhsd,bhtd->bhst", qt,
                            kb.astype(jnp.float32)) * s
        if causal:
            k_pos = src * Sl + lax.broadcasted_iota(jnp.int32, (Sl, Sl), 1)
            mask = q_pos >= k_pos
            logits = jnp.where(mask, logits, _MASKED)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        # fully-masked rows have logits == m_new == _MASKED ⇒ exp(0)=1; zero
        # them explicitly so dropped blocks contribute nothing
        p = jnp.where(logits <= _MASKED / 2, 0.0, p)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=-1)
        o = o * alpha[..., None] + jnp.einsum(
            "bhst,bhtd->bhsd", p, vb.astype(jnp.float32))
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
        return (o, m_new, l, kb, vb), None

    # mark the accumulators device-varying over the ring axis so the scan
    # carry type matches across iterations (they mix with the varying kv)
    def _vary(x):
        from .._jax_compat import pvary
        return pvary(x, (axis_name,))

    init = (
        _vary(jnp.zeros((B, H, Sl, D), jnp.float32)),
        _vary(jnp.full((B, H, Sl), _MASKED, jnp.float32)),
        _vary(jnp.zeros((B, H, Sl), jnp.float32)),
        kt, vt,
    )
    (o, m, l, _, _), _ = lax.scan(
        jax.checkpoint(step, prevent_cse=False), init, jnp.arange(n))
    out = o / jnp.maximum(l, 1e-20)[..., None]
    return jnp.einsum("bhsd->bshd", out).astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh, axis_name: str, causal=False,
                           scale=None, seq_dim: int = 1):
    """Global-array entry: shard q/k/v over `axis_name` on `seq_dim` and run
    the ring. q/k/v: [B, S, H, D] jax arrays (or anything with seq on dim 1).
    """
    from jax.sharding import PartitionSpec as P
    from .._jax_compat import shard_map

    spec_entries = [None] * q.ndim
    spec_entries[seq_dim] = axis_name
    spec = P(*spec_entries)
    fn = functools.partial(ring_attention_local, axis_name=axis_name,
                           causal=causal, scale=scale)
    return shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec)(q, k, v)
