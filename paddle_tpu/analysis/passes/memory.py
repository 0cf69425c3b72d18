"""Liveness-based peak-HBM estimation: OOM-before-compile.

A topological sweep over the abstract trace (and, for ``static.Program``
targets, the recorded DAG): every buffer is allocated at its producing
eqn and freed after its last use; the high-water mark of live bytes is
the predicted per-device peak. The model mirrors how XLA's buffer
assignment actually behaves on the programs this framework emits:

- **arguments** are live for the whole execution — except *donated*
  inputs, which free at their last use (the donation aliasing
  ``jax.jit(donate_argnums=...)`` buys);
- **fusion**: elementwise/view ops don't materialize — their outputs
  ride inside the consumer's fused loop (XLA duplicates cheap producers
  into every consumer), so only "anchor" buffers (matmuls, convs,
  scan-stacked residuals, collectives, gathers, custom calls) count;
- **remat** shows up structurally: ``jax.checkpoint`` forwards appear
  as ``remat2`` bodies, the *absence* of saved residuals is visible as
  smaller scan outputs, and a calibrated fraction of the body's outputs
  counts as recompute scratch;
- **scan** allocates its stacked outputs (the residual arrays the
  backward consumes — exactly the activation-memory term that separates
  GPipe from 1F1B) up front, plus one body-transient peak; loop carries
  materialize even when produced by ``jnp.zeros``, with a shadow-copy
  fraction for the double buffering XLA applies to in-place updates;
- ``shard_map`` bodies are per-shard already; outer vars divide by the
  mesh axes their PartitionSpec names (:func:`.cost.spec_divisor`).

Cross-checked against XLA's ``compiled.memory_analysis()`` by
``tools/mem_probe.py --compare-static`` (asserted within ±20% on every
combo of the tiny pipeline sweep by tests/test_analysis_cost.py).

Diagnostics:

- **PTMM001** (error) — predicted peak HBM exceeds the configured
  budget (``analyze(..., hbm_budget_gb=...)``; ``tools/check_program.py
  --hbm-budget-gb``, default 16 — the chip): the program OOMs before the
  first compile finishes burning your queue slot.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.extend.core as jex_core

from ..core import Diagnostic, register_pass
from .cost import _FUSABLE, _nbytes, _sub_jaxprs

# loop primitives whose carries/operands must materialize even when their
# producers would otherwise fuse away (a jnp.zeros carry init IS a real
# buffer for the whole loop)
_LOOPS = {"scan", "while"}

# Calibration constants, fitted once against XLA ``memory_analysis()``
# over the mem_probe tiny sweep (every schedule x remat combo lands
# within +-20%; asserted by tests/test_analysis_cost.py). Each one names
# a real buffer-assignment behavior observed in the HLO dumps, not a
# free fudge factor:
# _COND_MODE: how branch transients of a ``cond`` combine in the arena
#   ("max" — XLA shares exclusive branches' buffers by liveness).
# _LOOP_SHADOW: fraction of a loop's carry bytes double-buffered — XLA
#   shadows carries it cannot prove safe to update in place
#   (dynamic-update-slice rings and stacked accumulators show up at 2-3
#   distinct arena offsets in the 1f1b dump).
# _HO_OPERANDS: operands of higher-order calls (cond branches, remat
#   bodies) become computation parameters — real buffers — even when
#   their producers would otherwise fuse away.
# _REMAT_OUTS: fraction of a remat body's outputs live as recompute
#   scratch while the backward that consumes them is in flight.
# _SCAN_YS_ALIAS: a scan body's per-iteration ys slice writes straight
#   into the stacked output the outer frame already counts.
# _SCAN_YS_CORESIDENT: fraction of a scan's stacked ys charged as
#   co-resident with the body transient's peak — XLA allocates the
#   stack before the loop runs, but while-loop param/result aliasing
#   lets buffer assignment overlap much of it with body liveness, so
#   the calibrated effective fraction is well below 1.
_COND_MODE = "max"
_LOOP_SHADOW = 0.25
_HO_OPERANDS = True
_REMAT_OUTS = 0.2
_SCAN_YS_ALIAS = True
_SCAN_YS_CORESIDENT = 0.25

# higher-order call prims whose operands become computation parameters
# (real buffers) even when their producers would fuse
_HO_CALLS = {"cond", "remat", "remat2", "checkpoint", "jit",
             "closed_call", "core_call", "xla_call",
             "custom_jvp_call", "custom_vjp_call",
             "custom_jvp_call_jaxpr", "custom_vjp_call_jaxpr"}
_REMATS = {"remat", "remat2", "checkpoint"}


@dataclass
class MemoryEstimate:
    """Predicted per-device HBM profile of one analyzed target."""

    args_bytes: float = 0.0       # inputs (params+state+batch), per device
    temp_peak_bytes: float = 0.0  # peak transient above the arguments
    peak_bytes: float = 0.0       # args + temps high-water mark
    out_bytes: float = 0.0        # non-donation-aliased outputs
    donated_bytes: float = 0.0    # arg bytes eligible for reuse
    source: str = "jaxpr"         # jaxpr | program
    detail: dict = field(default_factory=dict)

    def as_dict(self):
        gb = 1024 ** 3
        return {
            "args_gb": round(self.args_bytes / gb, 4),
            "temp_peak_gb": round(self.temp_peak_bytes / gb, 4),
            "peak_gb": round(self.peak_bytes / gb, 4),
            "donated_gb": round(self.donated_bytes / gb, 4),
            "source": self.source,
        }


class _MemWalker:
    def __init__(self):
        self.peak_extra = 0.0  # high-water mark of live bytes above args

    # ------------------------------------------------------------------
    def walk(self, jaxpr, in_divs, freeable):
        """Sweep one jaxpr frame. ``in_divs``: device-partition count per
        invar. ``freeable``: id(var) -> bytes reclaimable at that var's
        last use (donated args; always all frame-local temps). Returns
        live-bytes delta at frame end (outputs still live)."""
        div = {}
        for v, d in zip(jaxpr.invars, in_divs):
            div[id(v)] = max(int(d or 1), 1)
        for v in jaxpr.constvars:
            div[id(v)] = 1

        def dof(v):
            if isinstance(v, jex_core.Literal):
                return 1
            return div.get(id(v), 1)

        last_use = {}
        anchor_consumers = {}  # id(var) -> consuming non-fusable eqns
        for i, eqn in enumerate(jaxpr.eqns):
            is_anchor = eqn.primitive.name not in _FUSABLE
            for v in eqn.invars:
                if not isinstance(v, jex_core.Literal):
                    last_use[id(v)] = i
                    if is_anchor:
                        anchor_consumers[id(v)] = \
                            anchor_consumers.get(id(v), 0) + 1
        n_eqns = len(jaxpr.eqns)
        for v in jaxpr.outvars:
            if not isinstance(v, jex_core.Literal):
                last_use[id(v)] = n_eqns  # never freed in this frame

        live = 0.0
        freeable = dict(freeable)  # id(var) -> bytes to reclaim at death

        def bump(candidate):
            self.peak_extra = max(self.peak_extra, candidate)

        for i, eqn in enumerate(jaxpr.eqns):
            name = eqn.primitive.name
            d_out = max([dof(v) for v in eqn.invars] or [1])
            for v in eqn.outvars:
                div[id(v)] = d_out

            # a loop's operands (carry inits, stacked xs) are REAL
            # buffers for the whole trip even when their producers would
            # fuse away (jnp.zeros grad accumulators, activation rings):
            # retro-materialize any fusable-produced operand here
            if name in _LOOPS or (_HO_OPERANDS and name in _HO_CALLS):
                for v in eqn.invars:
                    if (not isinstance(v, jex_core.Literal)
                            and freeable.get(id(v)) == 0.0):
                        b = _nbytes(v.aval) / max(dof(v), 1)
                        freeable[id(v)] = b
                        live += b

            # a higher-order body's transient peaks BEFORE the outer
            # frame owns its outputs (the body's last instruction writes
            # them), so bump first, then account the outputs
            shadow = 0.0
            if _LOOP_SHADOW and name in _LOOPS:
                shadow = _LOOP_SHADOW * self._carry_bytes(eqn, dof)
            if name == "scan" and _SCAN_YS_CORESIDENT:
                # XLA preallocates the stacked ys before the loop runs,
                # so the body transient co-resides with the stack (the
                # per-iteration slice it writes is already credited back
                # by _SCAN_YS_ALIAS)
                ncar = int(eqn.params.get("num_carry", 0) or 0)
                shadow += _SCAN_YS_CORESIDENT * sum(
                    _nbytes(v.aval) / max(dof(v), 1)
                    for v in eqn.outvars[ncar:]
                    if not isinstance(v, jax.core.DropVar))
            bump(live + shadow + self._call_transient(eqn, dof, live))

            for v in eqn.outvars:
                if isinstance(v, jax.core.DropVar):
                    continue
                # fusable outputs still materialize when 2+ anchors
                # consume them: XLA stores the buffer (softmax probs fed
                # to both the AV matmul and its backward) rather than
                # recompute the chain per consumer
                materialize = (name not in _FUSABLE
                               or anchor_consumers.get(id(v), 0) >= 2)
                b = (_nbytes(v.aval) / max(dof(v), 1)) if materialize \
                    else 0.0
                freeable[id(v)] = b
                live += b
            bump(live)

            for v in eqn.invars:
                if isinstance(v, jex_core.Literal):
                    continue
                if last_use.get(id(v)) == i and id(v) in freeable:
                    live -= freeable.pop(id(v))
        return live

    # ------------------------------------------------------------------
    @staticmethod
    def _carry_bytes(eqn, dof) -> float:
        """Bytes of a loop's carried state (scan carry / while carry —
        the part XLA may double-buffer), excluding consts and xs."""
        params = eqn.params
        if eqn.primitive.name == "scan":
            nc = int(params.get("num_consts", 0) or 0)
            ncar = int(params.get("num_carry", 0) or 0)
            carry = eqn.invars[nc:nc + ncar]
        else:  # while
            nc = (int(params.get("cond_nconsts", 0) or 0)
                  + int(params.get("body_nconsts", 0) or 0))
            carry = eqn.invars[nc:]
        return sum(_nbytes(v.aval) / max(dof(v), 1) for v in carry
                   if not isinstance(v, jex_core.Literal))

    def _call_transient(self, eqn, dof, live_base) -> float:
        """Transient bytes a higher-order eqn's body needs on top of the
        current live set (0 for first-order prims). Includes the body's
        own view of any outputs it produces."""
        name = eqn.primitive.name
        params = eqn.params

        def sub_peak(sub_jaxpr, in_divs):
            w = _MemWalker()
            w.walk(sub_jaxpr, in_divs, {})
            return w.peak_extra

        if name == "scan":
            body = params["jaxpr"].jaxpr
            peak = sub_peak(body, [dof(v) for v in eqn.invars])
            if _SCAN_YS_ALIAS:
                # the body's per-iteration ys slice is written straight
                # into the stacked output the outer frame already counts
                ncar = int(params.get("num_carry", 0) or 0)
                ys = body.outvars[ncar:]
                peak = max(0.0, peak - sum(
                    _nbytes(v.aval) for v in ys
                    if not isinstance(v, jex_core.Literal)))
            return peak
        if name == "while":
            nc = int(params.get("cond_nconsts", 0) or 0)
            body = params["body_jaxpr"].jaxpr
            return sub_peak(body, [dof(v) for v in eqn.invars[nc:]])
        if name == "cond":
            peaks = [sub_peak(br.jaxpr, [dof(v) for v in eqn.invars[1:]])
                     for br in params["branches"]]
            if not peaks:
                return 0.0
            return sum(peaks) if _COND_MODE == "sum" else max(peaks)
        if name == "shard_map":
            body = params["jaxpr"]
            return sub_peak(body, [1] * len(body.invars))
        subs = list(_sub_jaxprs(params))
        if subs:
            divs = [dof(v) for v in eqn.invars]
            peak = max(sub_peak(s, (divs + [1] * len(s.invars))
                                [:len(s.invars)]) for s in subs)
            if _REMAT_OUTS and name in _REMATS:
                # the rematerialized forward writes its residuals while
                # the backward that consumes them is in flight
                peak += _REMAT_OUTS * sum(
                    _nbytes(v.aval) / max(dof(v), 1)
                    for v in eqn.outvars
                    if not isinstance(v, jax.core.DropVar))
            return peak
        return 0.0


def estimate_jaxpr_peak(closed_jaxpr, in_divisors=None, donated=None,
                        ) -> MemoryEstimate:
    """Liveness-sweep one (Closed)Jaxpr into a :class:`MemoryEstimate`.

    ``in_divisors``: per-invar device-partition counts (see
    :func:`.cost.spec_divisor`); ``donated``: per-invar booleans — a
    donated arg's bytes free at its last use instead of pinning HBM for
    the whole step."""
    jaxpr = (closed_jaxpr.jaxpr
             if isinstance(closed_jaxpr, jex_core.ClosedJaxpr)
             else closed_jaxpr)
    divs = list(in_divisors or [])
    divs += [1] * (len(jaxpr.invars) - len(divs))
    don = list(donated or [])
    don += [False] * (len(jaxpr.invars) - len(don))

    est = MemoryEstimate()
    freeable = {}
    for v, d, dn in zip(jaxpr.invars, divs, don):
        b = _nbytes(v.aval) / max(int(d or 1), 1)
        est.args_bytes += b
        if dn:
            est.donated_bytes += b
            freeable[id(v)] = b
    consts = getattr(closed_jaxpr, "consts", None) or []
    for c in consts:
        est.args_bytes += _nbytes(c)

    w = _MemWalker()
    end_live = w.walk(jaxpr, divs, freeable)
    est.temp_peak_bytes = max(w.peak_extra, 0.0)
    est.peak_bytes = est.args_bytes + est.temp_peak_bytes
    est.out_bytes = max(end_live, 0.0)
    return est


def estimate_program_peak(prog, fetches=None) -> MemoryEstimate:
    """Liveness sweep over a recorded ``static.Program`` DAG: node
    outputs allocate at their producing node and free after their last
    consumer; feeds are arguments; fetches stay live to the end."""
    from ...framework.tensor import Tensor

    est = MemoryEstimate(source="program")
    nodes = list(prog._nodes)

    def out_key(t):
        lz = getattr(t, "_lazy", None)
        if lz is None or lz[0] == "feed":
            return None
        return (id(lz[0]), lz[1])

    last_use = {}
    for i, n in enumerate(nodes):
        for a in n.args:
            if isinstance(a, Tensor):
                k = out_key(a)
                if k is not None:
                    last_use[k] = i
    for t in (fetches or []):
        if isinstance(t, Tensor):
            k = out_key(t)
            if k is not None:
                last_use[k] = len(nodes)

    for name, t in getattr(prog, "_feeds", {}).items():
        v = getattr(t, "_value", None)
        if v is not None and hasattr(v, "shape"):
            est.args_bytes += _nbytes(v)

    live = 0.0
    peak = 0.0
    sizes = {}
    for i, n in enumerate(nodes):
        for idx, aval in enumerate(n.out_avals):
            b = float(_nbytes(aval))
            sizes[(id(n), idx)] = b
            live += b
        peak = max(peak, live)
        for a in n.args:
            if isinstance(a, Tensor):
                k = out_key(a)
                if k is not None and last_use.get(k) == i:
                    live -= sizes.pop(k, 0.0)
    est.temp_peak_bytes = peak
    est.peak_bytes = est.args_bytes + peak
    est.out_bytes = max(live, 0.0)
    return est


# ---------------------------------------------------------------------------
# the registered pass
# ---------------------------------------------------------------------------

@register_pass("memory", order=65)
def memory_pass(ctx):
    est = None
    if ctx.jaxpr is not None:
        est = estimate_jaxpr_peak(
            ctx.jaxpr,
            in_divisors=getattr(ctx, "in_divisors", None),
            donated=getattr(ctx, "donated_invars", None))
    elif ctx.program is not None:
        est = estimate_program_peak(ctx.program, ctx.fetches)
    if est is None:
        return []
    ctx.memory_estimate = est

    budget = getattr(ctx, "hbm_budget_bytes", None)
    if not budget or est.peak_bytes <= budget:
        return []
    gb = 1024 ** 3
    return [Diagnostic(
        "PTMM001", "memory", "error",
        f"predicted peak HBM {est.peak_bytes / gb:.2f} GiB exceeds the "
        f"{budget / gb:.2f} GiB budget "
        f"(arguments {est.args_bytes / gb:.2f} GiB + transient peak "
        f"{est.temp_peak_bytes / gb:.2f} GiB) — this program OOMs before "
        f"the first step; shard or donate more state, enable remat, or "
        f"shrink the micro-batch",
        extra={"memory": est.as_dict(),
               "budget_gb": round(budget / gb, 2)})]
