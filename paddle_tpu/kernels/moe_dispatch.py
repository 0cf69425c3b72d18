"""Fused Pallas MoE dispatch + combine kernels (TPU).

The EP-MoE hot path today is gate → int32 slot indices → gathers →
all_to_all → expert FFN → combine
(``incubate/distributed/models/moe/moe_layer.py``): the gate matmul, the
priority-major capacity counters, and the token scatter each
materialize HBM round-trips between XLA ops. "Cross-Platform Fused MoE
Dispatch in Triton" (PAPERS.md) fuses routing/permute/dispatch into one
kernel; this module is the Pallas equivalent:

- :func:`fused_moe_dispatch` — ONE kernel fusing the top-k gate
  (logits → f32 softmax → top-k → GShard priority-major
  capacity-clamped slot assignment) with the scatter of token rows into
  per-expert contiguous buffers ``[E, C, M]``. The gate reads ``x``
  once per priority and each kept row is copied once — the
  one-hot/cumsum position math and the padded, gathered copies that the
  unfused path streams through HBM are never made (the cost pass's
  PTCS004 diagnostic prices exactly this delta).
- :func:`fused_moe_combine` — the matching fused combine: weighted
  gather-sum of expert outputs back to token order, the combine indices
  and weights riding scalar prefetch so each grid step DMAs exactly one
  expert row (the paged-attention gather scheme applied to MoE
  un-permutation).

What the TPU's compiler asked for (the first version — 1-D per-token
values, ``(T, 1)`` index blocks, ``(1, M)`` row blocks, dynamic row
stores from vector registers — was refused on every count; see
``tests/test_chip_compile.py``): the gate is computed transposed,
``[E, T]`` with tokens on lanes, so every per-token quantity is a row
``[1, T]``, the ``[K, 1, S]`` index/weight outputs are written as they
come and no value is ever 1-D. Rows travel as ``[rows, 1, M]``: a
``(1, 1, M)`` block's last two dims are then the array's own, and a
one-row DMA slices an untiled dim (a one-row slice of a tiled
``[rows, M]`` buffer is refused: "Slice shape along dimension 0 must be
aligned to tiling (8)"). The scatter is one row DMA per kept token,
HBM to HBM, its destination read as a scalar from SMEM; rows narrower
than 32 bits travel packed as ``uint32``. Neither kernel has a time yet:
one small DMA (or grid step) per row is the plain form, and which path
wins where is a measurement (ROADMAP S7).

Semantics contract (asserted in tier-1 against the gather-based
reference, CPU interpret mode): identical to the unfused path for every
supported ``gate_kind`` —

========= ===========================================================
kind      combine weight of the k-th choice
========= ===========================================================
naive     raw gate logit (NaiveGate: no softmax, no renorm)
switch    softmax probability (SwitchGate, top-1)
gshard    softmax prob / (sum of top-k probs + 1e-9)  (GShardGate eval)
renorm    softmax prob / max(sum of top-k probs, 1e-9) (``ep_moe_ffn``)
========= ===========================================================

Capacity semantics are GShard's: all 1st choices claim expert slots
before any 2nd choice, ties broken in token order; a choice that
overflows its expert's ``capacity`` keeps its combine index at the
out-of-range sentinel ``E*C`` and contributes zero output (the combine
kernel skips the row). Aux-loss ingredients (``me`` = mean softmax
prob per expert, ``ce`` = top-1 load fraction) come out of the same
kernel so GShard/Switch training keeps its load-balance loss without
re-running the gate.

Training: both ops carry a ``jax.custom_vjp`` whose backward is the VJP
of the *reference* (gather-based) implementation, recomputed from the
saved primals — forward parity makes the pair consistent, so a fused
train run is trajectory-equivalent to the unfused one (asserted).

On CPU both kernels run in interpreter mode (tier-1 parity without a
TPU); on TPU the same ``pallas_call`` compiles (x64 off around the
trace), with the model dim padded to the 128-lane width and the expert
dim to 8 sublanes inside the wrapper.
"""
from __future__ import annotations

import contextlib
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._mosaic import x64_off

__all__ = ["fused_moe_dispatch", "fused_moe_combine",
           "reference_moe_dispatch", "reference_moe_combine",
           "dispatch_indices", "GATE_KINDS"]

_LANE = 128
_SUBLANE = 8
_NEG_INF = -1e30
GATE_KINDS = ("naive", "switch", "gshard", "renorm")

# kernel-name override: the auto-fusion rewrite (analysis.rewrite) tags
# the dispatch ``pallas_call`` it instantiates ("autofuse_..."), so the
# cost pass can tell a rewritten program (PTCS005) from the hand-wired
# ``MoELayer(fused_dispatch=True)`` path, which stays unnamed
_PALLAS_NAME = None


@contextlib.contextmanager
def pallas_kernel_name(name):
    """Name the dispatch ``pallas_call``s traced inside this context."""
    global _PALLAS_NAME
    prev = _PALLAS_NAME
    _PALLAS_NAME = name
    try:
        yield
    finally:
        _PALLAS_NAME = prev


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _pad_to(n: int, mult: int) -> int:
    return max(mult, ((n + mult - 1) // mult) * mult)


# ---------------------------------------------------------------------------
# reference (gather-based) implementation — the parity oracle AND the
# recompute-based backward of both fused ops. Pure jax, kept in this
# module so the kernels and their oracle are one import.
# ---------------------------------------------------------------------------

def dispatch_indices(idx, *, num_expert, capacity):
    """THE priority-major capacity-clamped slot assignment (GShard
    rule) — the single implementation shared by the fused kernels'
    reference/VJP AND ``MoELayer``'s gather path (one drop/priority
    semantics, one place to change it).

    ``idx [S, k]`` int32 expert choices (k = priority order). Returns
      slot_token ``[E*C]`` int32: token feeding each expert slot
      (``S`` = empty slot → the zero pad row),
      comb_idx ``[S, k]`` int32: flat ``expert*C + slot`` per choice
      (``E*C`` = dropped).
    """
    S, k = idx.shape
    E, C = num_expert, capacity
    # priority-major running per-expert counter: all 1st choices claim
    # capacity before any 2nd choice (GShard rule)
    oh = jax.nn.one_hot(idx.T, E, dtype=jnp.float32)           # [k, S, E]
    pos = jnp.cumsum(oh.reshape(k * S, E), axis=0) - 1.0
    e_f = idx.T.reshape(-1).astype(jnp.int32)
    slot_f = jnp.take_along_axis(
        pos, e_f[:, None], axis=1)[:, 0].astype(jnp.int32)
    within = slot_f < C
    token_f = jnp.tile(jnp.arange(S, dtype=jnp.int32), k)
    flat_ec = jnp.where(within, e_f * C + slot_f, E * C)
    # unique per (expert, slot) by construction of the running counter;
    # out-of-capacity entries scatter out of bounds and are dropped
    slot_token = jnp.full((E * C,), S, jnp.int32).at[flat_ec].set(
        token_f, mode="drop")
    return slot_token, flat_ec.reshape(k, S).T                  # [S, k]


def _gate_values(logits, probs, kind, top_k):
    """Top-k selection + combine weights for one ``gate_kind`` (see
    module docstring table). Selection runs over the logits (softmax is
    monotonic, so the order matches a top-k over the probs)."""
    lv, idx = jax.lax.top_k(logits, top_k)                      # [S, k]
    pv = jnp.take_along_axis(probs, idx, axis=1)
    if kind == "naive":
        val = lv.astype(jnp.float32)
    elif kind == "switch":
        val = pv
    elif kind == "gshard":
        val = pv / (jnp.sum(pv, -1, keepdims=True) + 1e-9)
    elif kind == "renorm":
        val = pv / jnp.maximum(jnp.sum(pv, -1, keepdims=True), 1e-9)
    else:
        raise ValueError(f"gate_kind {kind!r} not in {GATE_KINDS}")
    return val, idx.astype(jnp.int32)


def reference_moe_dispatch(x, gate_w, gate_b, *, num_expert, capacity,
                           top_k, gate_kind="gshard"):
    """Gather-based reference of :func:`fused_moe_dispatch` — identical
    math, unfused XLA ops. Returns ``(expert_in [E, C, M],
    comb_idx [S, k] int32, val [S, k] f32, me [E] f32, ce [E] f32)``."""
    S, M = x.shape
    E, C = num_expert, capacity
    logits = (x.astype(jnp.float32) @ gate_w.astype(jnp.float32)
              + gate_b.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    val, idx = _gate_values(logits, probs, gate_kind, top_k)
    slot_token, comb_idx = dispatch_indices(idx, num_expert=E,
                                            capacity=C)
    # scatter: slot ← token row (empty slots read the zero pad row)
    xp = jnp.concatenate([x, jnp.zeros((1, M), x.dtype)], axis=0)
    expert_in = xp[slot_token].reshape(E, C, M)
    me = jnp.mean(probs, axis=0)
    ce = jax.lax.stop_gradient(
        jnp.mean(jax.nn.one_hot(idx[:, 0], E, dtype=jnp.float32), axis=0))
    return expert_in, comb_idx, val, me, ce


def reference_moe_combine(expert_out_flat, val, comb_idx):
    """Gather-based reference of :func:`fused_moe_combine`:
    ``y[s] = sum_k val[s,k] * expert_out_flat[comb_idx[s,k]]`` with the
    ``E*C`` sentinel reading a zero pad row."""
    ep = jnp.concatenate(
        [expert_out_flat,
         jnp.zeros((1, expert_out_flat.shape[-1]),
                   expert_out_flat.dtype)], axis=0)
    g = ep[comb_idx]                                            # [S, k, M]
    return jnp.einsum("skm,sk->sm", g, val.astype(g.dtype))


# ---------------------------------------------------------------------------
# fused dispatch kernel
# ---------------------------------------------------------------------------

def _dispatch_kernel(x_ref, gw_ref, gb_ref, x_rows, _zeros, out_rows,
                     comb_ref, val_ref, me_ref, ce_ref, counts, flat_vm,
                     flat_sm, sems, *, S, E, E_pad, C, K, T, gate_kind):
    """One (priority p, token block b) step. Grid order is priority-
    major — every 1st choice in the batch claims capacity before any
    2nd choice (GShard), the running per-expert counters riding VMEM
    scratch across the whole walk.

    Everything per-token is a ROW ``[1, T]`` (tokens on lanes, experts
    on sublanes: the gate is computed transposed, ``[E_pad, T]``), so
    the ``[K, 1, S]`` index/weight outputs are written as they come and
    every reduction keeps its axis — no 1-D value, no relayout.

    ``x_ref`` is this block's ``[T, M]`` tile in VMEM (the gate reads
    it); ``x_rows`` / ``out_rows`` are the token rows and the expert
    buffer left in HBM as ``[rows, 1, M]`` — a row is then a slice along
    an untiled dim, which is what a one-row DMA needs. ``out_rows``
    aliases the zeros operand, so empty slots stay zero."""
    p = pl.program_id(0)
    blk = pl.program_id(1)
    f1, f0 = jnp.float32(1.0), jnp.float32(0.0)

    @pl.when((p == 0) & (blk == 0))
    def _():
        counts[...] = jnp.zeros_like(counts)
        me_ref[...] = jnp.zeros_like(me_ref)
        ce_ref[...] = jnp.zeros_like(ce_ref)

    x = x_ref[...]                                         # [T, M_pad]
    logits = jax.lax.dot_general(
        gw_ref[...], x.astype(jnp.float32), (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32) + gb_ref[...]  # [E_pad, T]
    row = jax.lax.broadcasted_iota(jnp.int32, (E_pad, T), 0)
    tok = blk * np.int32(T) + jax.lax.broadcasted_iota(
        jnp.int32, (1, T), 1)
    valid = tok < np.int32(S)                              # [1, T] pad mask
    # padding experts carry -inf logits: softmax ~0, never selected
    probs = jax.nn.softmax(logits, axis=0)

    # unrolled top-K (K static): masked-argmax rounds, ties at lowest
    # index exactly like lax.top_k
    work = logits
    idxs, lvals, pvals = [], [], []
    for _ in range(K):
        m = jnp.max(work, axis=0, keepdims=True)           # [1, T]
        sel = jnp.min(jnp.where(work >= m, row, np.int32(E_pad)),
                      axis=0, keepdims=True)               # [1, T]
        hit = row == sel
        idxs.append(sel)
        lvals.append(m)
        pvals.append(jnp.sum(jnp.where(hit, probs, f0), axis=0,
                             keepdims=True))
        work = jnp.where(hit, jnp.float32(_NEG_INF), work)

    denom = functools.reduce(jnp.add, pvals)

    def pick(vals):
        return functools.reduce(jnp.add, [
            jnp.where(p == i, v, jnp.zeros_like(v))
            for i, v in enumerate(vals)])

    chosen, p_sel, l_sel = pick(idxs), pick(pvals), pick(lvals)
    if gate_kind == "naive":
        v_sel = l_sel
    elif gate_kind == "switch":
        v_sel = p_sel
    elif gate_kind == "gshard":
        v_sel = p_sel / (denom + 1e-9)
    else:  # renorm
        v_sel = p_sel / jnp.maximum(denom, 1e-9)

    @pl.when(p == 0)
    def _():
        # aux-loss ingredients (sums; the wrapper divides by S): mean
        # softmax prob per expert + top-1 load counts, padding masked
        me_ref[...] += jnp.sum(jnp.where(valid, probs, f0), axis=1,
                               keepdims=True)
        ce_ref[...] += jnp.sum(
            jnp.where((row == idxs[0]) & valid, f1, f0), axis=1,
            keepdims=True)

    # priority-major running position: counter + within-block cumsum
    # (inclusive cumsum as an upper-triangular matmul — MXU-friendly)
    mine = row == chosen                                   # [E_pad, T]
    oh = jnp.where(mine & valid, f1, f0)
    tri = jnp.where(
        jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
        <= jax.lax.broadcasted_iota(jnp.int32, (T, T), 1), f1, f0)
    # 0/1 operands and counts below 2^24: exact at any matmul precision
    cum = jnp.dot(oh, tri, preferred_element_type=jnp.float32)  # [E_pad, T]
    slot = (jnp.sum(jnp.where(mine, counts[...] + cum, f0), axis=0,
                    keepdims=True) - f1).astype(jnp.int32)  # [1, T]
    counts[...] += jnp.sum(oh, axis=1, keepdims=True)
    within = valid & (slot < np.int32(C)) & (slot >= 0)
    flat = jnp.where(within, chosen * np.int32(C) + slot,
                     np.int32(E * C))
    comb_ref[0] = flat
    val_ref[0] = v_sel

    # the fused scatter: each kept token row goes to its expert slot by
    # one row DMA (dropped and padding rows carry the sentinel and are
    # skipped). The destinations are read as scalars, so the index row
    # takes the VMEM -> SMEM way first. All of a block's copies are
    # started, then all awaited.
    flat_vm[...] = flat
    to_smem = pltpu.make_async_copy(flat_vm, flat_sm, sems.at[0])
    to_smem.start()
    to_smem.wait()
    base = blk * np.int32(T)

    def row_copy(t):
        return pltpu.make_async_copy(
            x_rows.at[pl.ds(base + t, 1)],
            out_rows.at[pl.ds(flat_sm[0, t], 1)], sems.at[1])

    def start(t, _):
        @pl.when(flat_sm[0, t] < np.int32(E * C))
        def _():
            row_copy(t).start()
        return 0

    def wait(t, _):
        @pl.when(flat_sm[0, t] < np.int32(E * C))
        def _():
            row_copy(t).wait()
        return 0

    jax.lax.fori_loop(0, T, start, 0)
    jax.lax.fori_loop(0, T, wait, 0)


def _dispatch_pallas(x, gate_w, gate_b, num_expert, capacity, top_k,
                     gate_kind):
    S, M = x.shape
    E, C, K = int(num_expert), int(capacity), int(top_k)
    interp = _interpret()
    # interpret mode skips the padding (it would only slow the CPU
    # walk); on TPU the model dim pads to the 128-lane width and the
    # expert dim — sublanes of the transposed gate — to a multiple of 8
    E_pad = E if interp else _pad_to(E, _SUBLANE)
    # a one-row DMA moves whole 32-bit words: narrower rows travel as
    # uint32 (a packed row of one is half a tile to Mosaic)
    pack = 1 if interp else max(1, 4 // x.dtype.itemsize)
    M_pad = M if interp else _pad_to(M, _LANE * pack)
    T = S if S <= 128 else 128
    nblk = math.ceil(S / T)
    S_pad = nblk * T
    with x64_off(interp):
        # no-op pads are skipped entirely (they would read as extra HBM
        # anchors to the cost model and extra copies to XLA)
        xp = x if (S_pad == S and M_pad == M) \
            else jnp.pad(x, [(0, S_pad - S), (0, M_pad - M)])
        gwt = gate_w.astype(jnp.float32).T                 # [E, M]
        if M_pad != M or E_pad != E:
            gwt = jnp.pad(gwt, [(0, E_pad - E), (0, M_pad - M)])
        gbp = gate_b.astype(jnp.float32)
        if E_pad != E:
            gbp = jnp.pad(gbp, [(0, E_pad - E)],
                          constant_values=_NEG_INF)
        gbp = gbp[:, None]

        rows = xp if pack == 1 else jax.lax.bitcast_convert_type(
            xp.reshape(S_pad, M_pad // pack, pack), jnp.uint32)

        kernel = functools.partial(
            _dispatch_kernel, S=S, E=E, E_pad=E_pad, C=C, K=K, T=T,
            gate_kind=gate_kind)
        whole = lambda p, b: (0, 0)
        hbm = pl.BlockSpec(memory_space=pl.ANY)
        out, comb, val, me, ce = pl.pallas_call(
            kernel,
            grid=(K, nblk),
            in_specs=[
                pl.BlockSpec((T, M_pad), lambda p, b: (b, 0)),
                pl.BlockSpec((E_pad, M_pad), whole),
                pl.BlockSpec((E_pad, 1), whole),
                hbm,
                hbm,
            ],
            out_specs=[
                hbm,
                # [K, 1, S]: a (1, 1, T) block's last two dims are the
                # array's own / a lane multiple — (T, 1) of [S, K] is not
                pl.BlockSpec((1, 1, T), lambda p, b: (p, 0, b)),
                pl.BlockSpec((1, 1, T), lambda p, b: (p, 0, b)),
                pl.BlockSpec((E_pad, 1), whole),
                pl.BlockSpec((E_pad, 1), whole),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((E * C, 1, rows.shape[1]), rows.dtype),
                jax.ShapeDtypeStruct((K, 1, S_pad), jnp.int32),
                jax.ShapeDtypeStruct((K, 1, S_pad), jnp.float32),
                jax.ShapeDtypeStruct((E_pad, 1), jnp.float32),
                jax.ShapeDtypeStruct((E_pad, 1), jnp.float32),
            ],
            # the expert buffer starts as the zeros operand: empty slots
            # are never written
            input_output_aliases={4: 0},
            scratch_shapes=[pltpu.VMEM((E_pad, 1), jnp.float32),
                            pltpu.VMEM((1, T), jnp.int32),
                            pltpu.SMEM((1, T), jnp.int32),
                            pltpu.SemaphoreType.DMA((2,))],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=interp,
            name=_PALLAS_NAME,
        )(xp, gwt, gbp, rows[:, None, :],
          jnp.zeros((E * C, 1, rows.shape[1]), rows.dtype))
        out = out[:, 0, :]
        if pack > 1:
            out = jax.lax.bitcast_convert_type(out, x.dtype).reshape(
                E * C, M_pad)
    return (out.reshape(E, C, M_pad)[:, :, :M], comb[:, 0, :S].T,
            val[:, 0, :S].T, me[:E, 0] / jnp.float32(S),
            ce[:E, 0] / jnp.float32(S))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _fused_dispatch(x, gate_w, gate_b, num_expert, capacity, top_k,
                    gate_kind):
    return _dispatch_pallas(x, gate_w, gate_b, num_expert, capacity,
                            top_k, gate_kind)


def _fused_dispatch_fwd(x, gate_w, gate_b, num_expert, capacity, top_k,
                        gate_kind):
    out = _dispatch_pallas(x, gate_w, gate_b, num_expert, capacity,
                           top_k, gate_kind)
    return out, (x, gate_w, gate_b)


def _fused_dispatch_bwd(num_expert, capacity, top_k, gate_kind, res,
                        cts):
    # recompute-based backward THROUGH THE REFERENCE: forward parity
    # (asserted in tier-1) makes the pair consistent, so fused training
    # is trajectory-equivalent to the gather path
    x, gate_w, gate_b = res
    _, vjp = jax.vjp(
        functools.partial(reference_moe_dispatch, num_expert=num_expert,
                          capacity=capacity, top_k=top_k,
                          gate_kind=gate_kind), x, gate_w, gate_b)
    return vjp(cts)


_fused_dispatch.defvjp(_fused_dispatch_fwd, _fused_dispatch_bwd)


def fused_moe_dispatch(x, gate_w, gate_b, *, num_expert, capacity,
                       top_k, gate_kind="gshard"):
    """Fused gate + capacity-clamped scatter (see module docstring).

    ``x [S, M]``; ``gate_w [M, E]``; ``gate_b [E]``. Returns
    ``(expert_in [E, C, M], comb_idx [S, k] int32, val [S, k] f32,
    me [E] f32, ce [E] f32)`` — ``me``/``ce`` are the GShard aux-loss
    ingredients (mean softmax prob / top-1 load fraction per expert).
    Differentiable in ``x``/``gate_w``/``gate_b`` (reference-recompute
    VJP)."""
    if gate_kind not in GATE_KINDS:
        raise ValueError(f"gate_kind {gate_kind!r} not in {GATE_KINDS}")
    if top_k > num_expert:
        raise ValueError(f"top_k {top_k} > num_expert {num_expert}")
    return _fused_dispatch(x, gate_w, gate_b, int(num_expert),
                           int(capacity), int(top_k), gate_kind)


# ---------------------------------------------------------------------------
# fused combine kernel
# ---------------------------------------------------------------------------

def _combine_kernel(comb_ref, val_ref, eo_ref, o_ref, *, EC, S):
    s = pl.program_id(0)
    kk = pl.program_id(1)

    @pl.when(kk == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    i = kk * np.int32(S) + s

    @pl.when(comb_ref[i] < np.int32(EC))
    def _():
        o_ref[...] = (o_ref[...].astype(jnp.float32) + val_ref[i]
                      * eo_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _combine_pallas(expert_out_flat, val, comb_idx):
    EC, M = expert_out_flat.shape
    S, K = comb_idx.shape
    interp = _interpret()
    M_pad = M if interp else _pad_to(M, _LANE)
    with x64_off(interp):
        eo = expert_out_flat if M_pad == M \
            else jnp.pad(expert_out_flat, [(0, 0), (0, M_pad - M)])

        def row(s, k, comb, val):
            # the fused gather: the combine index picks which expert row
            # this grid step DMAs into VMEM (the drop sentinel clamps to
            # the last row and the kernel skips the accumulate)
            return (jnp.minimum(comb[k * S + s], EC - 1), 0, 0)

        # rows ride as [rows, 1, M]: a (1, 1, M) block's last two dims
        # are the array's own, which a (1, M) block of [rows, M] is not.
        # Indices and weights are scalars in SMEM, choice-major and flat
        # (a 2-D SMEM array pads its last dim to 128 words).
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S, K),
            in_specs=[pl.BlockSpec((1, 1, M_pad), row)],
            out_specs=pl.BlockSpec((1, 1, M_pad),
                                   lambda s, k, comb, val: (s, 0, 0)),
        )
        out = pl.pallas_call(
            functools.partial(_combine_kernel, EC=EC, S=S),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((S, 1, M_pad),
                                           expert_out_flat.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interp,
        )(comb_idx.astype(jnp.int32).T.reshape(-1),
          val.astype(jnp.float32).T.reshape(-1), eo[:, None, :])
    return out[:, 0, :M]


@jax.custom_vjp
def _fused_combine(expert_out_flat, val, comb_idx):
    return _combine_pallas(expert_out_flat, val, comb_idx)


def _fused_combine_fwd(expert_out_flat, val, comb_idx):
    return (_combine_pallas(expert_out_flat, val, comb_idx),
            (expert_out_flat, val, comb_idx))


def _fused_combine_bwd(res, ct):
    expert_out_flat, val, comb_idx = res
    _, vjp = jax.vjp(
        lambda eo, v: reference_moe_combine(eo, v, comb_idx),
        expert_out_flat, val)
    d_eo, d_val = vjp(ct)
    return d_eo, d_val, np.zeros(comb_idx.shape, jax.dtypes.float0)


_fused_combine.defvjp(_fused_combine_fwd, _fused_combine_bwd)


def fused_moe_combine(expert_out_flat, val, comb_idx):
    """Fused weighted gather-sum back to token order:
    ``y[s] = sum_k val[s,k] * expert_out_flat[comb_idx[s,k]]`` with the
    ``E*C`` sentinel contributing zero (dropped tokens). One expert row
    DMA per (token, choice) grid step — the combine indices ride scalar
    prefetch, so there is no [S, k, M] gathered intermediate in HBM.
    Differentiable in ``expert_out_flat``/``val``."""
    return _fused_combine(expert_out_flat, val, comb_idx)
