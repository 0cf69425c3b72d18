"""Auto-fusion: jaxpr pattern-match + rewrite (PTCS004 findings → Pallas).

The cost pass *finds* fusion opportunities (PTCS004: anchor-op chains
materializing glue HBM traffic a fused kernel would stream); this module
*acts* on them: it pattern-matches flagged chain shapes in a traced
program against a registry of rewrite rules and re-emits the program
with each matched eqn subgraph replaced by a template-instantiated
Pallas kernel call. ``estimate_jaxpr_cost`` then prices the rewritten
program and the PTCS004 row flips to a PTCS005 "fused by rule R" info
record carrying the predicted Δms.

Shipped rules:

- ``ragged_prefill`` — the chunk-prefill dense page gather
  (``k_pages[page_table]`` over one layer's pages + causal softmax
  attention: the XLA path of the chunk program) becomes
  :func:`~paddle_tpu.kernels.paged_attention.ragged_prefill_attention`:
  the page table rides scalar prefetch exactly like the decode kernel.
  (``ServingEngine(use_kernel=True)`` calls that kernel itself, on the
  whole pool with a layer index, and leaves the rule nothing to match.)
- ``int8_dequant_matmul`` — weight-only-int8 decode matmuls
  (``convert(int8→float) → dot_general → mul(scale)``) become
  :func:`~paddle_tpu.kernels.int8_matmul.int8_matmul`: dequant in
  registers on the MXU feed, no materialized dequantized weight.
- ``moe_gate_dispatch`` — any captured MoE variant's gate→dispatch
  glue (``top_k`` routing + one-hot/cumsum/gather/scatter chain),
  matched **by structure, not by model name**, becomes
  :func:`~paddle_tpu.kernels.moe_dispatch.fused_moe_dispatch` — the
  hand-wired ``MoELayer(fused_dispatch=True)`` kernel is now a
  rewrite-rule target.

Safety model — parity is the gatekeeper
---------------------------------------
Matching is deliberately *loose* (anchor op + backward/forward region
slice); the *mandatory interpret-mode parity check* is what makes a
rewrite trustworthy, in two stages per match:

1. **region vs oracle** — the matched subgraph is evaluated concretely
   on synthesized probe inputs and compared against the rule's pure-XLA
   oracle (the exact semantics the kernel implements) at the full match
   shapes. A near-miss chain that merely *looks* like the pattern fails
   here and is NOT rewritten.
2. **kernel vs oracle** — the Pallas template runs in interpret mode
   against the same oracle (size-capped, memoized per shape) so the
   kernel instantiation itself is verified before the transform is
   trusted.

Only a match passing both stages is applied; everything else fails
closed (the program is left untouched and the attempt is recorded).

Opt-outs: ``PADDLE_NO_AUTOFUSE`` (any non-empty value disables the pass
globally) and ``PADDLE_AUTOFUSE_SUPPRESS="site1,site2"`` (comma list of
site-id substrings; matches anchored at a suppressed site are recorded
as ``suppressed`` and skipped).

Authoring a rewrite rule
------------------------
A rule is a function ``match_<rule>(jaxpr) -> list[Match]`` registered
in ``_RULES``. The recipe:

1. **Anchor**: pick the one primitive the chain cannot exist without
   (``gather`` with a rank-4 paged operand, ``convert_element_type``
   from int8, ``top_k``) and scan ``jaxpr.eqns`` for it. Keep anchor
   conditions tight enough to skip look-alikes cheaply (embedding
   gathers are rank-2; collective-decompress converts never feed a
   ``dot_general`` within two hops).
2. **Boundary**: identify the region's input vars (the tensors the
   kernel will take) and output vars (every region-produced var the
   rest of the program consumes). Use :func:`_backward_region` (slice
   from outputs, stop at inputs — unexpected free vars either become
   inputs, like the traced ``q_offset``, or reject the match) or a
   forward closure over benign primitives (the MoE rule).
3. **Template**: build ``replacement(*inputs) -> [outputs]`` around the
   Pallas kernel, and ``oracle(*inputs)`` — the same math in plain XLA.
   Name the kernel's ``pallas_call`` ``autofuse_<rule>`` so the cost
   pass emits PTCS005 for rewritten programs.
4. **Probes**: return probe hints for inputs that cannot be random
   (page-table entries must index real pages). Parity does the rest —
   a wrong boundary or a semantic mismatch fails stage 1, a broken
   template fails stage 2, and the program is left alone.

The engine handles the generic parts: region ordering ("sink" check —
the replacement is emitted at the last region eqn, so no external
consumer may sit between region eqns), overlap dedup, suppression,
Δms pricing (region mini-jaxpr vs replacement, both through
``estimate_jaxpr_cost``), and rewriting inside ``scan``/``while``/
``cond``/``pjit``/``custom_{j,v}jp_call`` bodies (rebuilt around the
rewritten sub-program; ``shard_map`` and ``pallas_call`` bodies are
opaque — matches there are unreachable by design). Differentiation
through a rewritten program re-traces the primal only (custom AD rules
of transparently inlined calls are dropped) — serving/inference scope.
"""
from __future__ import annotations

import functools
import json
import math
import os
from dataclasses import dataclass, field

import numpy as np
import jax
import jax.extend.core as jex_core
import jax.numpy as jnp

from .passes.cost import (estimate_jaxpr_cost, eqn_site_id,
                          fusion_candidates)

# the true trace escape: parity evaluates concretely (pallas included)
# even while an outer jit is tracing the program
from jax._src.core import eval_context as _eval_context

__all__ = ["autofuse", "autofuse_enabled", "fired_records",
           "match_records", "reset_records", "export_records",
           "fired_delta", "suppressed_sites", "RULE_NAMES"]

RULE_NAMES = ("ragged_prefill", "int8_dequant_matmul",
              "moe_gate_dispatch")

# parity probe budget: matches bigger than this verify the region at
# full size but the kernel template on a size-capped instance (the
# template is shape-generic; the memoized small-shape interpret run
# asserts its math, the full-size region run asserts the match)
_KERNEL_PROBE_ELEMS = 1 << 22
_REGION_EQN_CAP = 400
_RECORD_CAP = 512

# ("tile": jnp.tile binds a primitive of its own on JAX 0.9)
_VIEW = {"reshape", "transpose", "convert_element_type", "squeeze",
         "expand_dims", "broadcast_in_dim", "tile"}

_REBUILDABLE = {"jit", "closed_call", "core_call", "remat", "remat2",
                "checkpoint", "custom_jvp_call", "custom_vjp_call",
                "scan", "while", "cond"}

_RECORDS: list[dict] = []


# ---------------------------------------------------------------------------
# gates + records
# ---------------------------------------------------------------------------

def autofuse_enabled() -> bool:
    """Global gate: ``PADDLE_NO_AUTOFUSE`` (non-empty) disables."""
    return not os.environ.get("PADDLE_NO_AUTOFUSE")


def suppressed_sites() -> tuple:
    """Per-site opt-out list from ``PADDLE_AUTOFUSE_SUPPRESS``."""
    raw = os.environ.get("PADDLE_AUTOFUSE_SUPPRESS", "")
    return tuple(s.strip() for s in raw.split(",") if s.strip())


def _is_suppressed(site: str) -> bool:
    return any(tok in site for tok in suppressed_sites())


def _record(rec: dict) -> dict:
    _RECORDS.append(rec)
    del _RECORDS[:-_RECORD_CAP]
    return rec


def match_records() -> list[dict]:
    """Every match attempt this process recorded (``status`` in
    ``fired | suppressed | parity_failed | unmatched | error``)."""
    return list(_RECORDS)


def fired_records() -> list[dict]:
    """The subset of :func:`match_records` that actually rewrote."""
    return [r for r in _RECORDS if r.get("status") == "fired"]


def reset_records() -> None:
    _RECORDS.clear()


def export_records(path: str) -> str:
    """Write this process's match records to ``path`` as JSON (the
    ``autofusion.json`` artifact the perf doctor joins against measured
    op attribution). Returns the path."""
    payload = {"records": [
        {k: (list(v) if isinstance(v, tuple) else v)
         for k, v in r.items()} for r in _RECORDS]}
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, default=str)
    return path


def fired_delta(rule: str):
    """Predicted Δstep-ms of the most recent fired match of ``rule``
    (the PTCS005 annotation source), or None."""
    for rec in reversed(_RECORDS):
        if rec.get("rule") == rule and rec.get("status") == "fired":
            return rec.get("predicted_delta_ms")
    return None


# ---------------------------------------------------------------------------
# jaxpr helpers
# ---------------------------------------------------------------------------

def _is_lit(v) -> bool:
    return isinstance(v, jex_core.Literal)


def _ins(eqn):
    return [v for v in eqn.invars if not _is_lit(v)]


def _sub_closed(eqn):
    """Every ClosedJaxpr carried by one eqn's params (branches, bodies)."""
    out = []
    for v in eqn.params.values():
        stack = [v]
        while stack:
            x = stack.pop()
            if isinstance(x, jex_core.ClosedJaxpr):
                out.append(x)
            elif isinstance(x, jex_core.Jaxpr):
                out.append(jex_core.ClosedJaxpr(x, ()))
            elif isinstance(x, (list, tuple)):
                stack.extend(x)
    return out


def _producers(jaxpr) -> dict:
    prod = {}
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            prod[id(v)] = eqn
    return prod


def _ext_src(v, prod, through=("convert_element_type",)):
    """Walk ``v`` back through single-input pass-through eqns to the
    underlying source var."""
    while True:
        eqn = prod.get(id(v))
        if eqn is None or eqn.primitive.name not in through:
            return v
        ins = _ins(eqn)
        if len(ins) != 1:
            return v
        v = ins[0]


def _index_root(v, prod):
    """Underlying index array behind jnp's negative-index wrapping
    (``select_n(lt(i,0), i, add(i,n))``) and reshape/broadcast chains."""
    _THRU = {"broadcast_in_dim", "reshape", "convert_element_type",
             "squeeze", "expand_dims"}
    for _ in range(16):
        eqn = prod.get(id(v))
        if eqn is None:
            return v
        name = eqn.primitive.name
        ins = _ins(eqn)
        if name in _THRU and len(ins) == 1:
            v = ins[0]
            continue
        if name in ("select_n", "add", "lt", "ge"):
            roots = {id(_index_root(u, prod)): _index_root(u, prod)
                     for u in ins}
            if len(roots) == 1:
                return next(iter(roots.values()))
            # select_n(pred, a, b): pred's root and the value roots all
            # collapse to the same var for the wrap pattern
            vals = [r for r in roots.values()]
            base = [r for r in vals if getattr(r.aval, "dtype", None)
                    is not None and r.aval.dtype.kind == "i"]
            if len({id(r) for r in base}) == 1 and base:
                return base[0]
            return v
        return v
    return v


def _backward_region(jaxpr, outvars, stop_vars):
    """Backward slice from ``outvars`` down to ``stop_vars``.

    Returns ``(region_eqns_in_program_order, free_vars)`` where
    ``free_vars`` are encountered vars that are neither produced inside
    the slice nor in ``stop_vars`` (jaxpr invars/constvars the match
    didn't declare — a rule may promote them to inputs or reject)."""
    prod = _producers(jaxpr)
    stop = {id(v) for v in stop_vars}
    seen, eqn_ids, free = set(), set(), []
    stack = [v for v in outvars]
    while stack:
        v = stack.pop()
        if id(v) in seen or id(v) in stop:
            continue
        seen.add(id(v))
        eqn = prod.get(id(v))
        if eqn is None:
            free.append(v)
            continue
        if id(eqn) in eqn_ids:
            continue
        eqn_ids.add(id(eqn))
        if len(eqn_ids) > _REGION_EQN_CAP:
            return None, None
        stack.extend(_ins(eqn))
    region = [e for e in jaxpr.eqns if id(e) in eqn_ids]
    return region, free


def _region_outputs(jaxpr, region):
    """Region-produced vars the rest of the program consumes (or that
    are jaxpr outputs), in production order."""
    rid = {id(e) for e in region}
    produced = {}
    for e in region:
        for v in e.outvars:
            if not isinstance(v, jax.core.DropVar):
                produced[id(v)] = v
    used = []
    used_ids = set()
    for e in jaxpr.eqns:
        if id(e) in rid:
            continue
        for v in e.invars:
            if id(v) in produced and id(v) not in used_ids:
                used_ids.add(id(v))
                used.append(produced[id(v)])
    for v in jaxpr.outvars:
        if id(v) in produced and id(v) not in used_ids:
            used_ids.add(id(v))
            used.append(produced[id(v)])
    return used


def _emit_index(jaxpr, region, invars):
    """Where the evaluator can emit the fused call: after every region
    input's producer, before the first external consumer of any region
    output. Returns the eqn index to emit at, or None when no such
    point exists (the region interleaves with its consumers)."""
    pos = {id(e): i for i, e in enumerate(jaxpr.eqns)}
    prod = _producers(jaxpr)
    max_in = -1
    for v in invars:
        e = prod.get(id(v))
        if e is not None:
            max_in = max(max_in, pos[id(e)])
    rid = {id(e) for e in region}
    produced = {id(v) for e in region for v in e.outvars}
    first_ext = len(jaxpr.eqns)
    for i, e in enumerate(jaxpr.eqns):
        if id(e) in rid:
            continue
        if any(id(v) in produced for v in e.invars):
            first_ext = i
            break
    if max_in >= first_ext:
        return None
    return max_in + 1


def _region_jaxpr(region, invars, outvars):
    return jex_core.ClosedJaxpr(
        jex_core.Jaxpr(constvars=[], invars=list(invars),
                       outvars=list(outvars), eqns=list(region),
                       effects=jax.core.no_effects), ())


def _eval_region(region_cj, args):
    return jax.core.eval_jaxpr(region_cj.jaxpr, region_cj.consts, *args)


# ---------------------------------------------------------------------------
# parity (the gatekeeper)
# ---------------------------------------------------------------------------

def _probe_for(aval, rng, hint=None):
    # materialize under the eval trace: plans are often built while an
    # outer jit is tracing, and a probe that binds into that trace
    # would poison the concrete parity evaluation
    with _eval_context():
        shape = tuple(getattr(aval, "shape", ()))
        dtype = np.dtype("float32") if str(aval.dtype) == "bfloat16" \
            else np.dtype(aval.dtype)
        if hint is not None and hint[0] == "index":
            arr = rng.randint(0, max(int(hint[1]), 1), shape)
            return jnp.asarray(arr.astype(np.int32)).astype(aval.dtype)
        if hint is not None and hint[0] == "scalar":
            return jnp.asarray(np.int64(hint[1])).astype(
                aval.dtype).reshape(shape)
        if dtype.kind == "f":
            arr = (rng.standard_normal(shape) * 0.5).astype(dtype)
        elif dtype.kind in "iu":
            arr = rng.randint(0, 3, shape).astype(dtype)
        elif dtype.kind == "b":
            arr = rng.randint(0, 2, shape).astype(bool)
        else:
            arr = np.zeros(shape, dtype)
        return jnp.asarray(arr).astype(aval.dtype)


def _close(a, b) -> bool:
    a, b = jnp.asarray(a), jnp.asarray(b)
    if a.shape != b.shape:
        return False
    if np.dtype(a.dtype).kind in "iub" or np.dtype(b.dtype).kind in "iub":
        return bool(jnp.array_equal(a, b))
    wide = any("16" in str(d) for d in (a.dtype, b.dtype))
    rtol, atol = (2e-2, 2e-2) if wide else (5e-4, 5e-5)
    return bool(jnp.allclose(a.astype(jnp.float32), b.astype(jnp.float32),
                             rtol=rtol, atol=atol))


def _host_device():
    try:
        return jax.devices("cpu")[0]
    except RuntimeError:      # JAX_PLATFORMS names the accelerator alone
        return None


def _parity(region_cj, m) -> bool:
    """Stage 1: the matched region == the rule's oracle on probe
    inputs, evaluated concretely (compile-time eval escapes any ambient
    trace, so plans can be built while an outer jit is tracing) and on
    the host backend where there is one. Both sides are plain XLA and
    the question is one of semantics; on the chip a *replayed* region
    cannot be asked for exact matmuls (its ``dot_general``s carry the
    precision they were traced with, by default a single bf16 pass), and
    the f32 tolerance is not loosened to let that through (first chip
    run of the chunked engine, PR 22: ``parity_failed`` at 5e-4)."""
    with _eval_context(), jax.default_device(_host_device()):
        rng = np.random.RandomState(20260807)
        probes = [_probe_for(v.aval, rng, m.probe_hints.get(i))
                  for i, v in enumerate(m.invars)]
        got = _eval_region(region_cj, probes)
        want = m.oracle(*probes)
        if not isinstance(want, (list, tuple)):
            want = [want]
        if len(got) != len(want):
            return False
        return all(_close(g, w) for g, w in zip(got, want))


_KERNEL_PARITY_CACHE: dict = {}


def _kernel_parity(key, thunk) -> bool:
    """Stage 2, memoized: kernel template (interpret mode) == oracle on
    a size-capped probe instance."""
    hit = _KERNEL_PARITY_CACHE.get(key)
    if hit is None:
        # a template that RAISES is not memoized as a mismatch: the
        # exception reaches _finish_match, which records it as an error.
        # Both sides are traced afresh here, so both can take exact f32
        # matmuls: the comparison is about semantics, not MXU rounding
        with _eval_context(), jax.default_matmul_precision("highest"):
            hit = bool(thunk())
        _KERNEL_PARITY_CACHE[key] = hit
    return hit


# ---------------------------------------------------------------------------
# Match + rules
# ---------------------------------------------------------------------------

@dataclass
class Match:
    rule: str
    kind: str
    site: str
    region: list
    invars: list
    outvars: list
    replacement: object          # callable(*invals) -> list
    oracle: object               # pure-XLA same-signature semantics
    probe_hints: dict = field(default_factory=dict)  # invar idx -> hint
    kernel_key: tuple = ()
    kernel_thunk: object = None
    meta: dict = field(default_factory=dict)
    predicted_delta_ms: float = None
    emit_idx: int = None


def _finish_match(jaxpr, m: Match):
    """Generic validation every rule's candidate goes through."""
    outs = _region_outputs(jaxpr, m.region)
    if [id(v) for v in outs] != [id(v) for v in m.outvars]:
        # the rule must account for every externally-consumed var
        if {id(v) for v in outs} - {id(v) for v in m.outvars}:
            return None
    if not m.region:
        return None
    m.emit_idx = _emit_index(jaxpr, m.region, m.invars)
    if m.emit_idx is None:
        return None
    region_cj = _region_jaxpr(m.region, m.invars, m.outvars)
    try:
        if not _parity(region_cj, m):
            return None
        if m.kernel_thunk is not None \
                and not _kernel_parity(m.kernel_key, m.kernel_thunk):
            return None
    except Exception as e:
        # declined like a mismatch, but recorded as what it is: a pass
        # that errors must not read like one that found nothing
        if os.environ.get("PADDLE_AUTOFUSE_DEBUG"):
            import traceback
            traceback.print_exc()
        m.meta = dict(m.meta, error=repr(e)[:300])
        return None
    try:
        # price the delta on the accelerator roofline: on a CPU host
        # (smoke / no-backend) the microbenched CPU spec is compute-
        # bound and would invert the fusion question — what we predict
        # is the TPU step saving, so fall back to the default chip
        # (PADDLE_CHIP_KIND still overrides via chip_specs)
        from ..observability.instrument import chip_specs
        chip = chip_specs()
        if chip.get("name") == "cpu":
            chip = chip_specs("v5p")
        s0 = estimate_jaxpr_cost(region_cj, chip=chip)
        rep = jax.make_jaxpr(lambda *a: tuple(m.replacement(*a)))(
            *[jax.ShapeDtypeStruct(v.aval.shape, v.aval.dtype)
              for v in m.invars])
        s1 = estimate_jaxpr_cost(rep, chip=chip)
        m.predicted_delta_ms = round(s0.step_ms - s1.step_ms, 6)
    except Exception:
        m.predicted_delta_ms = None
    return m


# ----- rule 1: ragged_prefill ----------------------------------------------

def _is_paged_gather(eqn) -> bool:
    if eqn.primitive.name != "gather":
        return False
    ins = _ins(eqn)
    if len(ins) != 2:
        return False
    op, idx = eqn.invars[0], eqn.invars[1]
    if getattr(op.aval, "ndim", 0) != 4 \
            or getattr(idx.aval, "ndim", 0) != 3:
        return False
    if np.dtype(idx.aval.dtype).kind not in "iu":
        return False
    ss = tuple(eqn.params.get("slice_sizes") or ())
    return ss == (1,) + tuple(op.aval.shape[1:])


def match_ragged_prefill(jaxpr) -> list:
    from ..kernels.paged_attention import (paged_prefill_attention,
                                           ragged_prefill_attention)
    prod = _producers(jaxpr)
    cons = {}
    for eqn in jaxpr.eqns:
        for v in eqn.invars:
            if not _is_lit(v):
                cons.setdefault(id(v), []).append(eqn)

    def fwd_view(v, want_shape, want_last=None):
        """Walk forward through view ops to a var with ``want_shape``."""
        for _ in range(8):
            if tuple(v.aval.shape) == tuple(want_shape):
                return v
            nxt = [e for e in cons.get(id(v), ())
                   if e.primitive.name in _VIEW and len(_ins(e)) == 1]
            if len(nxt) != 1:
                return None
            v = nxt[0].outvars[0]
        return None

    gathers = [e for e in jaxpr.eqns if _is_paged_gather(e)]
    by_root: dict = {}
    for g in gathers:
        root = _index_root(g.invars[1], prod)
        by_root.setdefault(id(root), (root, []))[1].append(g)

    out = []
    for root, gs in by_root.values():
        if len(gs) != 2:
            continue
        P, ps, nkv, d = gs[0].invars[0].aval.shape
        # classify: the k-gather's downstream dot takes an external
        # rank-4 q [B, C, nh, d]; the v-gather's takes the probs
        kq = []
        for g in gs:
            B = g.invars[1].aval.shape[0]
            npt = g.invars[1].aval.shape[1]
            kv = fwd_view(g.outvars[0], (B, npt * ps, nkv, d))
            if kv is None:
                continue
            dots = [e for e in cons.get(id(kv), ())
                    if e.primitive.name == "dot_general"]
            if len(dots) != 1:
                continue
            dot = dots[0]
            other = dot.invars[0] if dot.invars[1] is kv else dot.invars[1]
            kq.append((g, kv, dot, other))
        if len(kq) != 2:
            continue
        qs = [(g, kv, dot, other) for (g, kv, dot, other) in kq
              if getattr(other.aval, "ndim", 0) == 4
              and other.aval.shape[-1] == d
              and other.aval.shape[2] == nkv]
        vs = [t for t in kq if t[1] is not qs[0][1]] if len(qs) == 1 else []
        if len(qs) != 1 or len(vs) != 1:
            continue
        g_k, _, _, q = qs[0]
        g_v, _, dot_v, _ = vs[0]
        B, C, nh, _ = q.aval.shape
        if nh != nkv:
            continue  # kernel is g==1 only (no MQA/GQA repeat)
        out_v = fwd_view(dot_v.outvars[0], (B, C, nh, d))
        if out_v is None:
            continue
        kp, vp = g_k.invars[0], g_v.invars[0]
        pt = _index_root(g_k.invars[1], prod)
        stops = [q, kp, vp, pt]
        region, free = _backward_region(jaxpr, [out_v], stops)
        if region is None:
            continue
        off = None
        if len(free) == 1 and np.dtype(free[0].aval.dtype).kind in "iu" \
                and int(np.prod(free[0].aval.shape or (1,))) == 1:
            off = free[0]
        elif free:
            continue
        if off is None:
            continue  # constant-offset chunk: out of scope, fail closed
        invars = [q, kp, vp, pt, off]
        region, free = _backward_region(jaxpr, [out_v], invars)
        if region is None or free:
            continue
        npt = pt.aval.shape[1]
        t = npt * ps

        def replacement(q, kp, vp, pt, off):
            return [ragged_prefill_attention(q, kp, vp, pt, off)]

        def oracle(q, kp, vp, pt, off):
            return [paged_prefill_attention(q, kp, vp, pt, off)]

        if B * C * t * nh * d <= _KERNEL_PROBE_ELEMS:
            kB, kC, kP = B, C, P
            knpt = npt
        else:
            kB, kC, kP = 1, min(C, 64), min(P, 32)
            knpt = min(npt, -(-kC // ps) + 1)

        def kernel_thunk(_B=kB, _C=kC, _P=kP, _npt=knpt, _nh=nh, _d=d,
                         _ps=ps, _dt=q.aval.dtype):
            rng = np.random.RandomState(7)
            q_ = jnp.asarray(rng.standard_normal(
                (_B, _C, _nh, _d)).astype(np.float32)).astype(_dt)
            kp_ = jnp.asarray(rng.standard_normal(
                (_P, _ps, _nh, _d)).astype(np.float32)).astype(_dt)
            vp_ = jnp.asarray(rng.standard_normal(
                (_P, _ps, _nh, _d)).astype(np.float32)).astype(_dt)
            pt_ = jnp.asarray(rng.randint(0, _P, (_B, _npt))
                              .astype(np.int32))
            off_ = jnp.int32(min(3, max(0, _npt * _ps - _C)))
            got = ragged_prefill_attention(q_, kp_, vp_, pt_, off_,
                                           interpret=True)
            want = paged_prefill_attention(q_, kp_, vp_, pt_, off_)
            return _close(got, want)

        out.append(Match(
            rule="ragged_prefill", kind="paged_attention",
            site=eqn_site_id(g_k), region=region, invars=invars,
            outvars=[out_v], replacement=replacement, oracle=oracle,
            probe_hints={3: ("index", P),
                         4: ("scalar", max(0, min(3, t - C)))},
            kernel_key=("ragged_prefill", kB, kC, nh, d, kP, ps, knpt,
                        str(q.aval.dtype)),
            kernel_thunk=kernel_thunk,
            meta={"B": B, "C": C, "nh": nh, "d": d, "pages": P,
                  "page_size": ps}))
    return out


# ----- rule 2: int8_dequant_matmul -----------------------------------------

def match_int8_dequant_matmul(jaxpr) -> list:
    from ..kernels.int8_matmul import int8_matmul
    prod = _producers(jaxpr)
    cons = {}
    for eqn in jaxpr.eqns:
        for v in eqn.invars:
            if not _is_lit(v):
                cons.setdefault(id(v), []).append(eqn)

    out = []
    for cvt in jaxpr.eqns:
        if cvt.primitive.name != "convert_element_type":
            continue
        src = cvt.invars[0]
        if _is_lit(src) or str(src.aval.dtype) != "int8":
            continue
        if np.dtype(cvt.outvars[0].aval.dtype).kind != "f":
            continue
        # the dequantized weight must feed a dot within <= 2 hops
        # (collective-decompress converts don't — they feed mul/add glue)
        dots = [e for e in cons.get(id(cvt.outvars[0]), ())
                if e.primitive.name == "dot_general"]
        if len(dots) != 1:
            continue
        dot = dots[0]
        wv = cvt.outvars[0]
        if dot.invars[1] is not wv:
            continue  # engines put the weight on the rhs
        x = dot.invars[0]
        if _is_lit(x) or np.dtype(x.aval.dtype).kind != "f":
            continue
        (lc, rc), (lb, rb) = dot.params["dimension_numbers"]
        if lb or rb:
            continue
        wq = src
        # scale: the dot output is multiplied by a broadcast
        # per-output-channel scale
        muls = [e for e in cons.get(id(dot.outvars[0]), ())
                if e.primitive.name == "mul"]
        if len(muls) != 1:
            continue
        mul = muls[0]
        other = mul.invars[0] if mul.invars[1] is dot.outvars[0] \
            else mul.invars[1]
        if _is_lit(other):
            continue
        bc = prod.get(id(other))
        if bc is None or bc.primitive.name != "broadcast_in_dim":
            continue
        ws = _ext_src(bc.invars[0], prod)
        if _is_lit(ws) or np.dtype(ws.aval.dtype).kind != "f":
            continue
        w_free = [i for i in range(wq.aval.ndim) if i not in rc]
        x_free = [i for i in range(x.aval.ndim) if i not in lc]
        N = int(np.prod([wq.aval.shape[i] for i in w_free] or [1]))
        K = int(np.prod([wq.aval.shape[i] for i in rc]))
        M = int(np.prod([x.aval.shape[i] for i in x_free] or [1]))
        ws_shape = tuple(s for s in ws.aval.shape if s != 1)
        if int(np.prod(ws.aval.shape or (1,))) != N \
                or ws_shape != tuple(wq.aval.shape[i] for i in w_free
                                     if wq.aval.shape[i] != 1):
            continue
        out_v = mul.outvars[0]
        invars = [x, wq, ws]
        region, free = _backward_region(jaxpr, [out_v], invars)
        if region is None or free:
            continue
        out_shape = tuple(out_v.aval.shape)
        out_dtype = out_v.aval.dtype
        x_perm = tuple(x_free) + tuple(lc)
        w_perm = tuple(rc) + tuple(w_free)

        def as2d(xa, wa, sa, _xp=x_perm, _wp=w_perm, _M=M, _K=K, _N=N):
            x2 = jnp.transpose(xa, _xp).reshape(_M, _K)
            w2 = jnp.transpose(wa, _wp).reshape(_K, _N)
            return x2, w2, sa.reshape(_N)

        def replacement(xa, wa, sa, _f=as2d, _os=out_shape,
                        _od=out_dtype):
            x2, w2, s1 = _f(xa, wa, sa)
            y = int8_matmul(x2, w2, s1)
            return [y.reshape(_os).astype(_od)]

        def oracle(xa, wa, sa, _f=as2d, _os=out_shape, _od=out_dtype):
            x2, w2, s1 = _f(xa, wa, sa)
            y = (x2 @ w2.astype(x2.dtype)) * s1.astype(x2.dtype)
            return [y.reshape(_os).astype(_od)]

        kM, kK, kN = min(M, 64), min(K, 512), min(N, 512)

        def kernel_thunk(_M=kM, _K=kK, _N=kN):
            rng = np.random.RandomState(11)
            x_ = jnp.asarray(rng.standard_normal(
                (_M, _K)).astype(np.float32))
            w_ = jnp.asarray(rng.randint(-127, 127, (_K, _N))
                             .astype(np.int8))
            s_ = jnp.asarray(rng.rand(_N).astype(np.float32))
            got = int8_matmul(x_, w_, s_, interpret=True)
            want = (x_ @ w_.astype(jnp.float32)) * s_
            return _close(got, want)

        m = Match(
            rule="int8_dequant_matmul", kind="dequant_matmul",
            site=eqn_site_id(dot), region=region, invars=invars,
            outvars=[out_v], replacement=replacement, oracle=oracle,
            kernel_key=("int8_dequant_matmul", kM, kK, kN),
            kernel_thunk=kernel_thunk,
            meta={"M": M, "K": K, "N": N})
        out.append(m)
    return out


# ----- rule 3: moe_gate_dispatch -------------------------------------------

# primitives the gate→dispatch glue is allowed to consist of; anything
# else (dot_general, conv, pallas_call, control flow) terminates the
# forward closure and marks its tainted inputs as region outputs
_MOE_GLUE = _VIEW | {
    "top_k", "cumsum", "sort", "gather", "scatter", "scatter-add",
    "scatter_add", "concatenate", "pad", "slice", "dynamic_slice",
    "iota", "select_n", "eq", "ne", "lt", "le", "gt", "ge",
    "stop_gradient", "add", "sub", "mul", "div", "max", "min", "exp",
    "log", "reduce_sum", "reduce_max", "reduce_min", "and", "or",
    "not", "rem", "floor", "clamp", "sign", "argmax", "argmin",
    "reduce_and", "reduce_or", "integer_pow", "square", "rsqrt", "sqrt",
}


def _benign_pjit(eqn) -> bool:
    if eqn.primitive.name != "jit":
        return False

    def ok(j):
        for e in j.eqns:
            if e.primitive.name == "jit":
                if not all(ok(c.jaxpr) for c in _sub_closed(e)):
                    return False
            elif e.primitive.name not in _MOE_GLUE:
                return False
        return True
    return all(ok(c.jaxpr) for c in _sub_closed(eqn))


def match_moe_gate_dispatch(jaxpr) -> list:
    from ..kernels.moe_dispatch import (GATE_KINDS, fused_moe_dispatch,
                                        pallas_kernel_name,
                                        reference_moe_dispatch)
    prod = _producers(jaxpr)
    out = []
    for tk in jaxpr.eqns:
        if tk.primitive.name != "top_k":
            continue
        logits = tk.invars[0]
        if _is_lit(logits) or getattr(logits.aval, "ndim", 0) != 2:
            continue
        # gate params: logits = x @ gate_w + gate_b (converts optional)
        adde = prod.get(id(logits))
        if adde is None or adde.primitive.name != "add":
            continue
        dot = gb = None
        seed_eqns = [adde]
        for v in _ins(adde):
            e = prod.get(id(v))
            chain = []
            while e is not None and e.primitive.name in (
                    "convert_element_type", "broadcast_in_dim", "reshape"):
                chain.append(e)
                nxt = _ins(e)
                if len(nxt) != 1:
                    break
                v2 = nxt[0]
                e2 = prod.get(id(v2))
                if e2 is None:
                    e = None
                    v = v2
                    break
                e, v = e2, v2
            if e is not None and e.primitive.name == "dot_general":
                dot = e
                seed_eqns += chain + [e]
            else:
                gb = v
                seed_eqns += chain
        if dot is None or gb is None:
            continue
        x = _ext_src(dot.invars[0], prod)
        gw = _ext_src(dot.invars[1], prod)
        for e in (prod.get(id(dot.invars[0])), prod.get(id(dot.invars[1]))):
            if e is not None and e.primitive.name == "convert_element_type":
                seed_eqns.append(e)
        if _is_lit(x) or _is_lit(gw) or _is_lit(gb):
            continue
        if getattr(x.aval, "ndim", 0) != 2 \
                or getattr(gw.aval, "ndim", 0) != 2:
            continue
        S, M = x.aval.shape
        E = gw.aval.shape[1]
        if logits.aval.shape != (S, E) or gw.aval.shape != (M, E):
            continue
        K = int(tk.params.get("k", 0) or 0)
        if not K:
            continue
        boundary_in = {id(x), id(gw), id(gb)}

        # forward closure over glue prims; external reads are OK only
        # when their backward slice is absorbable (terminates at
        # literals/iota/boundary inputs through glue prims)
        absorb_memo: dict = {}

        def absorbable(v):
            if id(v) in absorb_memo:
                return absorb_memo[id(v)]
            res: set = set()
            stack, seen = [v], set()
            ok = True
            while stack and ok:
                u = stack.pop()
                if id(u) in seen or id(u) in boundary_in:
                    continue
                seen.add(id(u))
                e = prod.get(id(u))
                if e is None:
                    ok = False  # external jaxpr invar/constvar
                    break
                nm = e.primitive.name
                if nm not in _MOE_GLUE and not _benign_pjit(e):
                    ok = False
                    break
                res.add(id(e))
                if len(res) > 50:
                    ok = False
                    break
                stack.extend(_ins(e))
            absorb_memo[id(v)] = res if ok else None
            return absorb_memo[id(v)]

        region_ids = {id(e) for e in seed_eqns}
        tainted = {id(logits)}
        for e in seed_eqns:
            for v in e.outvars:
                tainted.add(id(v))
        for eqn in jaxpr.eqns:
            if id(eqn) in region_ids:
                continue
            ins = _ins(eqn)
            if not any(id(v) in tainted for v in ins):
                continue
            nm = eqn.primitive.name
            if nm not in _MOE_GLUE and not _benign_pjit(eqn):
                continue  # consumer: boundary crossing
            need = []
            fits = True
            for v in ins:
                if id(v) in tainted or id(v) in boundary_in:
                    continue
                ab = absorbable(v)
                if ab is None:
                    fits = False
                    break
                need.append(ab)
            if not fits:
                continue
            region_ids.add(id(eqn))
            for ab in need:
                region_ids |= ab
            for v in eqn.outvars:
                tainted.add(id(v))
        # peel: the greedy closure may swallow glue-shaped consumers of
        # the dispatch results (reductions, aux-loss math). Any region
        # output whose aval doesn't map onto a fused_moe_dispatch
        # return ejects its producer (and that producer's region
        # descendants) back into the surrounding program, until every
        # output is mappable — or a core eqn would have to go (reject).
        def role_of(v):
            sh = tuple(v.aval.shape)
            kd = np.dtype(v.aval.dtype).kind
            if len(sh) == 3 and sh[0] == E and sh[2] == M and kd == "f":
                return "expert_in"
            if sh == (S, K) and kd in "iu":
                return "comb_idx"
            if sh == (S, K) and kd == "f":
                return "val"
            if sh == (E,) and kd == "f":
                return "me_ce"
            return None

        seed_ids = {id(e) for e in seed_eqns} | {id(tk)}
        region = None
        for _ in range(64):
            cand_region = [e for e in jaxpr.eqns if id(e) in region_ids]
            outs = _region_outputs(jaxpr, cand_region)
            bad = [v for v in outs if role_of(v) is None]
            if not bad:
                region = cand_region
                break
            prod_map = {id(v): e for e in cand_region
                        for v in e.outvars}
            peel_e = prod_map.get(id(bad[0]))
            if peel_e is None or id(peel_e) in seed_ids:
                break
            drop = {id(peel_e)}
            dropped_vars = {id(v) for v in peel_e.outvars}
            changed = True
            while changed:
                changed = False
                for e in cand_region:
                    if id(e) in drop:
                        continue
                    if any(id(v) in dropped_vars for v in e.invars):
                        drop.add(id(e))
                        dropped_vars |= {id(v) for v in e.outvars}
                        changed = True
            region_ids -= drop
        if region is None or not outs:
            continue

        # map boundary outputs onto fused_moe_dispatch's returns
        idx_var = tk.outvars[1]
        desc = {id(idx_var)}
        for e in region:
            if any(id(v) in desc for v in e.invars):
                for v in e.outvars:
                    desc.add(id(v))
        C = None
        roles = []
        e_vars = []
        for v in outs:
            sh = tuple(v.aval.shape)
            kd = np.dtype(v.aval.dtype).kind
            if len(sh) == 3 and sh[0] == E and sh[2] == M and kd == "f":
                roles.append("expert_in")
                C = sh[1]
            elif sh == (S, K) and kd in "iu":
                roles.append("comb_idx")
            elif sh == (S, K) and kd == "f":
                roles.append("val")
            elif sh == (E,) and kd == "f":
                roles.append("ce" if id(v) in desc else "me")
            else:
                roles.append(None)
            e_vars.append(v)
        if C is None or None in roles or len(set(roles)) != len(roles):
            continue
        order = {"expert_in": 0, "comb_idx": 1, "val": 2, "me": 3,
                 "ce": 4}
        picks = [order[r] for r in roles]
        region_cj = _region_jaxpr(region, [x, gw, gb], e_vars)

        # gate-kind identification doubles as stage-1 parity: the first
        # kind whose reference output matches the region wins; none
        # matching = a near-miss chain -> not rewritten
        rng = np.random.RandomState(20260807)
        probes = [_probe_for(v.aval, rng) for v in (x, gw, gb)]
        kind = None
        try:
            with _eval_context():
                got = _eval_region(region_cj, probes)
                for cand in GATE_KINDS:
                    ref = reference_moe_dispatch(
                        *probes, num_expert=E, capacity=C, top_k=K,
                        gate_kind=cand)
                    if all(_close(g, ref[p]) for g, p in zip(got, picks)):
                        kind = cand
                        break
        except Exception:
            if os.environ.get("PADDLE_AUTOFUSE_DEBUG"):
                import traceback
                traceback.print_exc()
            kind = None
        if kind is None:
            continue

        def replacement(xa, gwa, gba, _k=kind, _p=tuple(picks),
                        _E=E, _C=C, _K=K):
            with pallas_kernel_name("autofuse_moe_gate_dispatch"):
                full = fused_moe_dispatch(xa, gwa, gba, num_expert=_E,
                                          capacity=_C, top_k=_K,
                                          gate_kind=_k)
            return [full[i] for i in _p]

        def oracle(xa, gwa, gba, _k=kind, _p=tuple(picks),
                   _E=E, _C=C, _K=K):
            full = reference_moe_dispatch(xa, gwa, gba, num_expert=_E,
                                          capacity=_C, top_k=_K,
                                          gate_kind=_k)
            return [full[i] for i in _p]

        kS, kC = min(S, 128), min(C, 64)

        def kernel_thunk(_k=kind, _E=E, _C=kC, _K=K, _S=kS, _M=min(M, 128)):
            rng = np.random.RandomState(13)
            x_ = jnp.asarray(rng.standard_normal(
                (_S, _M)).astype(np.float32))
            gw_ = jnp.asarray(rng.standard_normal(
                (_M, _E)).astype(np.float32))
            gb_ = jnp.asarray(rng.standard_normal(_E).astype(np.float32))
            got = fused_moe_dispatch(x_, gw_, gb_, num_expert=_E,
                                     capacity=_C, top_k=_K, gate_kind=_k)
            want = reference_moe_dispatch(x_, gw_, gb_, num_expert=_E,
                                          capacity=_C, top_k=_K,
                                          gate_kind=_k)
            return all(_close(g, w) for g, w in zip(got, want))

        m = Match(
            rule="moe_gate_dispatch", kind="moe_dispatch",
            site=eqn_site_id(tk), region=region, invars=[x, gw, gb],
            outvars=e_vars, replacement=replacement, oracle=oracle,
            kernel_key=("moe_gate_dispatch", kS, min(M, 128), E, kC, K,
                        kind),
            kernel_thunk=kernel_thunk,
            meta={"S": S, "M": M, "E": E, "C": C, "k": K,
                  "gate_kind": kind})
        out.append(m)
    return out


_RULES = (match_ragged_prefill, match_int8_dequant_matmul,
          match_moe_gate_dispatch)


# ---------------------------------------------------------------------------
# plan building
# ---------------------------------------------------------------------------

@dataclass
class Plan:
    closed: object               # the traced ClosedJaxpr
    out_tree: object
    by_level: dict = field(default_factory=dict)   # id(jaxpr) -> [Match]
    dirty: set = field(default_factory=set)        # id(jaxpr) with matches below
    records: list = field(default_factory=list)

    @property
    def fired(self):
        return [r for r in self.records if r["status"] == "fired"]


def _plan_level(jaxpr, plan: Plan, label: str) -> bool:
    matches = []
    for rule_fn in _RULES:
        try:
            cands = rule_fn(jaxpr)
        except Exception as e:  # a broken matcher must not break tracing
            plan.records.append(_record({
                "label": label, "site": "<matcher>",
                "rule": rule_fn.__name__, "kind": "?", "status": "error",
                "detail": repr(e)[:200]}))
            continue
        for m in cands:
            if _is_suppressed(m.site):
                plan.records.append(_record({
                    "label": label, "site": m.site, "rule": m.rule,
                    "kind": m.kind, "status": "suppressed",
                    "meta": m.meta}))
                continue
            ok = _finish_match(jaxpr, m)
            if ok is None:
                plan.records.append(_record({
                    "label": label, "site": m.site, "rule": m.rule,
                    "kind": m.kind,
                    "status": "error" if "error" in m.meta
                    else "parity_failed",
                    "meta": m.meta}))
                continue
            matches.append(ok)
    # overlap dedup: first match wins, later overlapping ones drop
    taken: set = set()
    kept = []
    for m in matches:
        rid = {id(e) for e in m.region}
        if rid & taken:
            continue
        taken |= rid
        kept.append(m)
        plan.records.append(_record({
            "label": label, "site": m.site, "rule": m.rule,
            "kind": m.kind, "status": "fired",
            "predicted_delta_ms": m.predicted_delta_ms,
            "out_shapes": [tuple(v.aval.shape) for v in m.outvars],
            "meta": m.meta}))
    if kept:
        plan.by_level[id(jaxpr)] = kept
    dirty = bool(kept)
    consumed = taken
    for eqn in jaxpr.eqns:
        if id(eqn) in consumed:
            continue
        if eqn.primitive.name not in _REBUILDABLE:
            continue
        for sub in _sub_closed(eqn):
            if _plan_level(sub.jaxpr, plan, label):
                dirty = True
    if dirty:
        plan.dirty.add(id(jaxpr))
    # PTCS004-style candidates with no rule fired at this level surface
    # as "unmatched" (the op_audit --fusion coverage view)
    try:
        for cand in fusion_candidates(jaxpr, recurse=False):
            sites = cand.get("sites") or []
            covered = any(m.site in sites or any(
                s == m.site for s in sites) for m in kept)
            hit_rules = {m.kind for m in kept}
            if not covered and cand.get("kind", "moe_dispatch") \
                    not in hit_rules:
                plan.records.append(_record({
                    "label": label,
                    "site": sites[0] if sites else "<unknown>",
                    "rule": None, "kind": cand.get("kind"),
                    "status": "unmatched",
                    "glue_bytes": cand.get("glue_bytes")}))
    except Exception:
        pass
    return dirty


# ---------------------------------------------------------------------------
# the rewriting evaluator
# ---------------------------------------------------------------------------

def _run(jaxpr, consts, args, plan: Plan):
    env = {}

    def read(v):
        return v.val if _is_lit(v) else env[id(v)]

    for v, c in zip(jaxpr.constvars, consts):
        env[id(v)] = c
    for v, a in zip(jaxpr.invars, args):
        env[id(v)] = a

    matches = plan.by_level.get(id(jaxpr), ())
    consumed: dict = {}
    emit_at: dict = {}
    for m in matches:
        for e in m.region:
            consumed[id(e)] = m
        emit_at.setdefault(m.emit_idx, []).append(m)

    def emit(m):
        outs = m.replacement(*[read(v) for v in m.invars])
        for v, val in zip(m.outvars, outs):
            env[id(v)] = val

    for i, eqn in enumerate(jaxpr.eqns):
        for m in emit_at.get(i, ()):
            emit(m)
        if id(eqn) in consumed:
            continue
        invals = [read(v) for v in eqn.invars]
        if any(id(sub.jaxpr) in plan.dirty for sub in _sub_closed(eqn)):
            outs = _rebuild(eqn, invals, plan)
        else:
            subfuns, bp = eqn.primitive.get_bind_params(eqn.params)
            outs = eqn.primitive.bind(*subfuns, *invals, **bp)
            if not eqn.primitive.multiple_results:
                outs = [outs]
        for v, val in zip(eqn.outvars, outs):
            if not isinstance(v, jax.core.DropVar):
                env[id(v)] = val
    for m in emit_at.get(len(jaxpr.eqns), ()):
        emit(m)
    return [read(v) for v in jaxpr.outvars]


def _rebuild(eqn, invals, plan: Plan):
    """Re-emit one higher-order eqn around its rewritten body."""
    name = eqn.primitive.name
    params = eqn.params
    if name == "scan":
        nc = int(params["num_consts"])
        ncar = int(params["num_carry"])
        cj = params["jaxpr"]
        consts_v = invals[:nc]
        carry0 = tuple(invals[nc:nc + ncar])
        xs = tuple(invals[nc + ncar:])

        def body(carry, x):
            outs = _run(cj.jaxpr, cj.consts,
                        [*consts_v, *carry, *x], plan)
            return tuple(outs[:ncar]), tuple(outs[ncar:])

        carry_out, ys = jax.lax.scan(
            body, carry0, xs, length=int(params["length"]),
            reverse=bool(params.get("reverse", False)),
            unroll=params.get("unroll", 1) or 1)
        return [*carry_out, *ys]
    if name == "while":
        cn = int(params["cond_nconsts"])
        bn = int(params["body_nconsts"])
        ccj, bcj = params["cond_jaxpr"], params["body_jaxpr"]
        cconsts = invals[:cn]
        bconsts = invals[cn:cn + bn]
        carry = tuple(invals[cn + bn:])
        out = jax.lax.while_loop(
            lambda c: _run(ccj.jaxpr, ccj.consts,
                           [*cconsts, *c], plan)[0],
            lambda c: tuple(_run(bcj.jaxpr, bcj.consts,
                                 [*bconsts, *c], plan)),
            carry)
        return list(out)
    if name == "cond":
        idx, *ops = invals
        branches = [
            (lambda br: lambda *a: tuple(_run(br.jaxpr, br.consts,
                                              list(a), plan)))(br)
            for br in params["branches"]]
        out = jax.lax.switch(idx, branches, *ops)
        return list(out) if isinstance(out, (list, tuple)) else [out]
    # pjit / call-likes / custom_{j,v}jp: inline the (primal) body
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        cj = params.get(key)
        if isinstance(cj, jex_core.Jaxpr):
            cj = jex_core.ClosedJaxpr(cj, ())
        if isinstance(cj, jex_core.ClosedJaxpr) \
                and len(cj.jaxpr.invars) == len(invals):
            return _run(cj.jaxpr, cj.consts, invals, plan)
    # fallback: bind untouched (matches below stay unapplied)
    subfuns, bp = eqn.primitive.get_bind_params(eqn.params)
    outs = eqn.primitive.bind(*subfuns, *invals, **bp)
    return outs if eqn.primitive.multiple_results else [outs]


# ---------------------------------------------------------------------------
# the public wrapper
# ---------------------------------------------------------------------------

def _is_arrayish(x) -> bool:
    return isinstance(x, (jax.Array, np.ndarray, jax.core.Tracer))


class _AutoFused:
    """Signature-preserving wrapper: per input-shape-signature, trace
    ``fn`` once, build a rewrite plan (match + parity), and re-emit the
    rewritten program on every call; falls back to ``fn`` verbatim when
    disabled, when nothing matches, or when planning fails."""

    def __init__(self, fn, label=None):
        self.fn = fn
        self.label = label or getattr(fn, "__name__", "fn")
        self._plans: dict = {}
        functools.update_wrapper(self, fn,
                                 assigned=("__name__", "__doc__"),
                                 updated=())

    def plan_for(self, *args, **kwargs):
        """The plan this call signature resolves to (building it on
        first use); None when planning failed."""
        leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
        arr_idx = [i for i, l in enumerate(leaves) if _is_arrayish(l)]
        statics = tuple((i, repr(l)) for i, l in enumerate(leaves)
                        if i not in set(arr_idx))
        sig = (treedef,
               tuple((tuple(np.shape(leaves[i])),
                      str(jnp.asarray(leaves[i]).dtype)
                      if not hasattr(leaves[i], "dtype")
                      else str(leaves[i].dtype)) for i in arr_idx),
               statics)
        if sig in self._plans:
            return self._plans[sig], arr_idx, treedef, leaves
        static_leaves = {i: leaves[i] for i in range(len(leaves))
                         if i not in set(arr_idx)}

        def fn_flat(*arrs):
            full = list(leaves)
            for i, a in zip(arr_idx, arrs):
                full[i] = a
            for i, s in static_leaves.items():
                full[i] = s
            a2, k2 = jax.tree_util.tree_unflatten(treedef, full)
            return self.fn(*a2, **k2)

        plan = None
        try:
            avals = [jax.ShapeDtypeStruct(np.shape(leaves[i]),
                                          leaves[i].dtype)
                     for i in arr_idx]
            closed, out_shape = jax.make_jaxpr(
                fn_flat, return_shape=True)(*avals)
            plan = Plan(closed=closed,
                        out_tree=jax.tree_util.tree_structure(out_shape))
            _plan_level(closed.jaxpr, plan, self.label)
            plan.fn_flat = fn_flat
        except Exception as e:
            _record({"label": self.label, "site": "<plan>", "rule": None,
                     "kind": None, "status": "error",
                     "detail": repr(e)[:300]})
            plan = None
        self._plans[sig] = plan
        return plan, arr_idx, treedef, leaves

    def __call__(self, *args, **kwargs):
        if not autofuse_enabled():
            return self.fn(*args, **kwargs)
        plan, arr_idx, treedef, leaves = self.plan_for(*args, **kwargs)
        if plan is None or not plan.by_level:
            return self.fn(*args, **kwargs)
        flat = _run(plan.closed.jaxpr, plan.closed.consts,
                    [leaves[i] for i in arr_idx], plan)
        return jax.tree_util.tree_unflatten(plan.out_tree, flat)

    def records(self, *args, **kwargs):
        """Build (or reuse) the plan for this signature and return its
        match records."""
        plan, *_ = self.plan_for(*args, **kwargs)
        return list(plan.records) if plan is not None else []


def autofuse(fn, label=None):
    """Wrap ``fn`` so every call (re)emits the auto-fused program —
    the rewrite-then-compile entry point (wrap BEFORE ``jax.jit``; the
    wrapper preserves positional structure, so ``donate_argnums`` /
    ``static_argnums`` on the outer jit keep their meaning)."""
    if isinstance(fn, _AutoFused):
        return fn
    return _AutoFused(fn, label=label)
