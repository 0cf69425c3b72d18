"""Static cost model: sharding-aware FLOPs/bytes over jaxprs + roofline.

The role XLA's analytical cost modeling plays for the compiler, exposed
as a lint pass: every primitive in the abstract trace is charged FLOPs
and HBM bytes, sub-jaxprs included (``scan`` multiplies its body by the
trip count, ``cond`` takes the widest branch), and the totals roll up
into a roofline step-time / predicted-MFU against the same per-chip peak
table bench.py measures against (:func:`..observability.instrument
.chip_specs` — one table, one answer).

Sharding model (per-DEVICE cost, matching the per-chip numbers bench
emits): every jaxpr var carries a *divisor* — the number of devices its
data is partitioned over. Analyzer-provided input divisors (from
PartitionSpecs) propagate through eqns (an op's work divides by the mesh
axes its output is partitioned over); ``shard_map`` bodies are already
per-shard, so they count verbatim with divisor 1. Collectives are costed
by the bidirectional-ring model — an allreduce of ``b`` bytes over ``n``
ranks moves ``2(n-1)/n × b`` per device on the wire (the EQuARX lens) —
both for in-jit prims (psum/all_gather/...) and for the eager
``distributed.collective`` ledger the trace recorded.

Wire-dtype model (EQuARX): every collective is priced at its payload's
wire bytes — compressed collectives (int8 avals in the jaxpr, or eager
ledger records carrying ``wire_dtype``) automatically cost less, and a
``wire_dtype=`` override re-prices the WHOLE schedule at that dtype so
"what would int8 wire save" is a pure function of the trace. The
summary always carries the int8 what-if (``comm_bytes_int8`` /
``comm_ms_int8`` / ``bound_if_int8``), which PTCS001 reports and
``distributed.auto_enable_compression`` consumes.

Diagnostics:

- **PTCS001** (warning) — comm-bound step: predicted interconnect time
  exceeds both compute and HBM time. The collective schedule, not the
  math, sets the step time — re-shard or overlap before burning chips.
  Carries the int8-compression what-if in ``extra["whatif_int8"]``.
- **PTCS002** (info) — low arithmetic intensity: FLOPs/HBM-byte below
  the chip's ridge point on a non-trivial program — the MXU waits on
  HBM; fuse, batch, or cast down.
- **PTCS003** (info) — compression would flip the bound: the step is
  comm-bound at the current wire dtype but int8-compressed collectives
  (``new_group(compress="int8")`` / ``prims.c_*_q``) would make it
  compute- or HBM-bound — the cheapest predicted win on the table.
- **PTCS004** (info) — fusion opportunity: an unfused gate→dispatch
  chain (top-k routing followed by materialized cumsum/gather/scatter
  glue — the MoE dispatch shape) charges >2× the HBM traffic a fused
  dispatch kernel would stream (read the tokens once, write the expert
  buffers once). Neptune's locality lens applied to the fusion-aware
  HBM model: the glue ops are *anchors* XLA cannot fuse away, so the
  round-trips are real. ``kernels.moe_dispatch.fused_moe_dispatch`` /
  ``MoELayer(fused_dispatch=True)`` is the fused path; a ``pallas_call``
  never fires this (it IS the fused form, and is priced as one anchor:
  body FLOPs × grid steps, HBM = the call's operands + results).
"""
from __future__ import annotations

import math
import os
from dataclasses import dataclass, field

import numpy as np
import jax
import jax.extend.core as jex_core

from ..core import Diagnostic, register_pass
from ..tracing import eqn_site

# interchange-format / view ops: zero FLOPs, zero bytes (XLA folds them
# into layouts or fuses them away entirely)
_FREE = {
    "reshape", "squeeze", "expand_dims", "broadcast_in_dim", "iota",
    "stop_gradient", "copy", "device_put", "sharding_constraint",
    "transpose", "rev", "bitcast_convert_type", "split", "symbolic_zeros",
    "tile",  # what jnp.tile binds: a broadcast, like broadcast_in_dim
}

# elementwise / cheap ops XLA fuses into their consumers: their outputs
# never hit HBM as standalone buffers — shared with the liveness memory
# model (one fusion judgment, one answer)
_FUSABLE = _FREE | {
    "add", "sub", "mul", "div", "rem", "pow", "integer_pow", "neg",
    "sign", "abs", "max", "min", "and", "or", "xor", "not", "shift_left",
    "shift_right_logical", "shift_right_arithmetic",
    "exp", "log", "log1p", "expm1", "tanh", "sin", "cos", "tan", "sqrt",
    "rsqrt", "cbrt", "logistic", "erf", "erfc", "erf_inv", "floor",
    "ceil", "round", "is_finite", "square",
    "eq", "ne", "lt", "le", "gt", "ge", "select_n", "clamp",
    "convert_element_type", "real", "imag", "conj",
    "add_any", "pad", "slice", "dynamic_slice", "squeeze",
    "reduce_sum", "reduce_max", "reduce_min", "reduce_and", "reduce_or",
    "reduce_prod", "argmax", "argmin", "reduce_precision",
    "nextafter", "atan2", "axis_index", "random_seed", "random_wrap",
    "random_unwrap", "random_fold_in",
}

# primitives whose params carry sub-jaxprs the walker recurses into
# transparently (cost of the call = cost of the body)
_TRANSPARENT = {
    "jit", "closed_call", "core_call", "xla_call", "remat", "remat2",
    "checkpoint", "custom_jvp_call", "custom_vjp_call",
    "custom_vjp_call_jaxpr", "custom_jvp_call_jaxpr", "name",
}

# in-jit collective primitives -> wire-byte model over the axis size n,
# applied to the INPUT avals' bytes b. ring allreduce: reduce-scatter +
# all-gather = 2(n-1)/n of the payload (input == full payload); scatter
# phases move (n-1)/n of their full-sized input; all_gather's input is
# the per-shard payload, so each device receives (n-1) shards; ppermute
# is one full-payload hop.
_COLLECTIVES = {
    "psum": lambda b, n: 2.0 * (n - 1) / n * b,
    "pmax": lambda b, n: 2.0 * (n - 1) / n * b,
    "pmin": lambda b, n: 2.0 * (n - 1) / n * b,
    "all_gather": lambda b, n: (n - 1) * b,
    "reduce_scatter": lambda b, n: (n - 1) / n * b,
    "psum_scatter": lambda b, n: (n - 1) / n * b,
    "all_to_all": lambda b, n: (n - 1) / n * b,
    "ppermute": lambda b, n: float(b),
    "pbroadcast": lambda b, n: float(b),
}

# eager distributed.collective ledger ops -> same ring model (bytes are
# the recorded payload; gather-shaped ops scale by the group size)
_EAGER_COLLECTIVES = {
    "all_reduce": lambda b, n: 2.0 * (n - 1) / n * b,
    "reduce": lambda b, n: (n - 1) / n * b,
    "broadcast": lambda b, n: (n - 1) / n * b,
    "all_gather": lambda b, n: (n - 1) * b,       # payload is per-rank
    "all_gather_object": lambda b, n: (n - 1) * b,
    "reduce_scatter": lambda b, n: (n - 1) / n * b,
    "scatter": lambda b, n: (n - 1) / n * b,
    "all_to_all": lambda b, n: (n - 1) / n * b,
    "isend": lambda b, n: float(b),
    "send": lambda b, n: float(b),
    "irecv": lambda b, n: float(b),
    "recv": lambda b, n: float(b),
    "barrier": lambda b, n: 0.0,
}

def _compressed_nbytes(nbytes, itemsize, wire_dtype):
    """Wire bytes of a logical payload under int8/bf16 compression —
    shared with :mod:`paddle_tpu.distributed.compress` (one formula,
    one answer)."""
    from ...distributed.compress import compressed_nbytes
    return compressed_nbytes(nbytes, itemsize, wire_dtype)


def _floating_dtype(dtype) -> bool:
    """Mirror of the runtime's ``wire_for_dtype`` float-only rule, so
    the what-if never promises savings on integer/bool payloads the
    compressed path will refuse to quantize. String-based so bfloat16
    (not a numpy-native dtype) classifies correctly."""
    s = str(dtype)
    return "float" in s or s.startswith("bf")


# sustained-MXU efficiency knob: a raw peak-FLOPs roofline predicts 100%
# MFU, which no real schedule reaches; 0.55 is calibrated against the
# measured 345M/1.3B rows in BENCH_r0x (50-57% MFU) so predicted and
# measured step times land in the same regime. A chip dict carrying its
# own ``mxu_efficiency`` (a fitted ``observability.calibration`` file
# behind PADDLE_COST_CALIBRATION) overrides this default in
# :meth:`CostSummary.finalize`.
MXU_EFFICIENCY = 0.55


# ---------------------------------------------------------------------------
# site keys + op families (the attribution join keys opprof uses)
# ---------------------------------------------------------------------------

# op families the calibration fits per-family correction factors over;
# the scatter_gather set deliberately matches the PTCS004 glue ops plus
# the routing/index prims feeding them, so a family-level drift verdict
# speaks to the same ops the fusion diagnostic ranks
_FAMILY_DOT = {"dot_general", "conv_general_dilated"}
_FAMILY_SCATTER = {"cumsum", "gather", "scatter", "scatter-add",
                   "scatter_add", "sort", "concatenate",
                   "dynamic_update_slice", "top_k", "argsort"}


def op_family(name: str) -> str:
    """Coarse family of one primitive: ``dot`` | ``scatter_gather`` |
    ``collective`` | ``pallas`` | ``elementwise`` | ``other`` — the
    granularity the cost-model calibration fits correction factors at
    (finer would overfit a single trace, coarser can't name what's
    mispriced)."""
    if name in _FAMILY_DOT:
        return "dot"
    if name == "pallas_call":
        return "pallas"
    if name in _COLLECTIVES or name in _EAGER_COLLECTIVES:
        return "collective"
    if name in _FAMILY_SCATTER:
        return "scatter_gather"
    if name in _FUSABLE:
        return "elementwise"
    return "other"


def eqn_site_id(eqn) -> str:
    """Stable per-call-site key for one eqn: ``file.py:L123:prim`` from
    the user-frame source info (:func:`..tracing.eqn_site`), or
    ``<trace>:prim`` when no user frame survives. This string is the
    join key between the cost walk's predicted rows, the replay
    harness's measured rows, and (sanitized) the ``jax.named_scope``
    ids a real-chip profiler trace carries."""
    fname, line = eqn_site(eqn)
    prim = eqn.primitive.name
    if fname:
        return f"{os.path.basename(str(fname))}:L{line}:{prim}"
    return f"<trace>:{prim}"


def _nbytes(aval) -> int:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    try:
        itemsize = np.dtype(dtype).itemsize
    except TypeError:
        # extended dtypes (PRNG key<fry> etc.) aren't numpy dtypes
        itemsize = getattr(dtype, "itemsize", 4)
    try:
        return int(np.prod(shape, dtype=np.int64)) * itemsize
    except TypeError:
        return 0


def _nelems(aval) -> int:
    shape = getattr(aval, "shape", None)
    if shape is None:
        return 0
    try:
        return int(np.prod(shape, dtype=np.int64))
    except TypeError:
        return 0


@dataclass
class CostSummary:
    """Per-device cost rollup + roofline verdict for one analyzed target."""

    flops: float = 0.0            # per-device FLOPs per step
    hbm_bytes: float = 0.0        # per-device HBM traffic per step
    comm_bytes: float = 0.0       # per-device wire bytes per step
    comm_bytes_int8: float = 0.0  # what-if: same schedule, int8 wire
    wire_dtype: str | None = None  # forced wire dtype, if any
    by_prim: dict = field(default_factory=dict)  # name -> [flops, bytes, n]
    # site -> [flops, hbm_bytes, comm_bytes, count, family] — the per-eqn
    # export the op-attribution layer joins measured traces against
    by_site: dict = field(default_factory=dict)
    chip: dict = field(default_factory=dict)
    compute_ms: float = 0.0
    hbm_ms: float = 0.0
    comm_ms: float = 0.0
    comm_ms_int8: float = 0.0
    step_ms: float = 0.0
    bound: str = "compute"        # compute | memory | comm
    bound_if_int8: str = "compute"
    predicted_mfu: float = 0.0
    arithmetic_intensity: float = 0.0
    ridge: float = 0.0            # chip ridge point, FLOPs per HBM byte

    def finalize(self, chip: dict):
        self.chip = dict(chip)
        eff_peak = chip["peak_flops"] * chip.get("mxu_efficiency",
                                                 MXU_EFFICIENCY)
        self.compute_ms = 1e3 * self.flops / eff_peak
        self.hbm_ms = 1e3 * self.hbm_bytes / chip["hbm_bw"]
        self.comm_ms = 1e3 * self.comm_bytes / chip["ici_bw"]
        self.step_ms = max(self.compute_ms, self.hbm_ms, self.comm_ms,
                           1e-9)
        self.bound = {self.compute_ms: "compute", self.hbm_ms: "memory",
                      self.comm_ms: "comm"}[
            max(self.compute_ms, self.hbm_ms, self.comm_ms)]
        # the compression what-if: identical schedule, int8 wire
        self.comm_ms_int8 = 1e3 * self.comm_bytes_int8 / chip["ici_bw"]
        self.bound_if_int8 = {
            self.compute_ms: "compute", self.hbm_ms: "memory",
            self.comm_ms_int8: "comm"}[
            max(self.compute_ms, self.hbm_ms, self.comm_ms_int8)]
        self.predicted_mfu = (self.flops / (self.step_ms / 1e3)
                              / chip["peak_flops"]) if self.flops else 0.0
        self.arithmetic_intensity = (self.flops / self.hbm_bytes
                                     if self.hbm_bytes else 0.0)
        self.ridge = chip["peak_flops"] / chip["hbm_bw"]
        return self

    @property
    def int8_wire_reduction(self):
        """Predicted wire-bytes reduction of int8 compression (>= 1)."""
        if not self.comm_bytes or not self.comm_bytes_int8:
            return 1.0
        return self.comm_bytes / self.comm_bytes_int8

    def as_dict(self):
        return {
            "flops": self.flops, "hbm_bytes": self.hbm_bytes,
            "comm_bytes": self.comm_bytes,
            "comm_bytes_int8": self.comm_bytes_int8,
            "int8_wire_reduction": round(self.int8_wire_reduction, 3),
            "wire_dtype": self.wire_dtype,
            "compute_ms": round(self.compute_ms, 4),
            "hbm_ms": round(self.hbm_ms, 4),
            "comm_ms": round(self.comm_ms, 4),
            "comm_ms_int8": round(self.comm_ms_int8, 4),
            "step_ms": round(self.step_ms, 4), "bound": self.bound,
            "bound_if_int8": self.bound_if_int8,
            "predicted_mfu": round(self.predicted_mfu, 4),
            "arithmetic_intensity": round(self.arithmetic_intensity, 2),
            "chip": self.chip.get("name"),
        }


# ---------------------------------------------------------------------------
# per-primitive FLOPs (global, pre-division); bytes default to in+out
# ---------------------------------------------------------------------------

def _dot_general_flops(eqn) -> float:
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    batch = math.prod(lhs.shape[i] for i in lb) if lb else 1
    contract = math.prod(lhs.shape[i] for i in lc) if lc else 1
    lhs_free = math.prod(
        d for i, d in enumerate(lhs.shape) if i not in lc and i not in lb)
    rhs_free = math.prod(
        d for i, d in enumerate(rhs.shape) if i not in rc and i not in rb)
    return 2.0 * batch * lhs_free * rhs_free * contract


def _conv_flops(eqn) -> float:
    dn = eqn.params["dimension_numbers"]
    groups = int(eqn.params.get("feature_group_count", 1) or 1)
    out = eqn.outvars[0].aval
    rhs = eqn.invars[1].aval
    k_spatial = math.prod(rhs.shape[i] for i in dn.rhs_spec[2:])
    in_ch = rhs.shape[dn.rhs_spec[1]]  # already per-group
    del groups  # in_ch from rhs_spec is per-group by construction
    return 2.0 * math.prod(out.shape) * in_ch * k_spatial


def _default_flops(eqn):
    """Elementwise/reduce fallback: one FLOP per output element (per
    input element for reductions)."""
    flops = float(sum(_nelems(v.aval) for v in eqn.outvars))
    if eqn.primitive.name.startswith("reduce_"):
        flops = float(sum(_nelems(v.aval) for v in eqn.invars
                          if hasattr(v.aval, "shape")))
    return flops


def _sub_jaxprs(params):
    for v in params.values():
        stack = [v]
        while stack:
            x = stack.pop()
            if isinstance(x, jex_core.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, jex_core.Jaxpr):
                yield x
            elif isinstance(x, (list, tuple)):
                stack.extend(x)


def _axis_size(axes, axis_sizes, default=1):
    if axes is None:
        return default
    if isinstance(axes, (str, int)):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= int(axis_sizes.get(a, default))
    return max(n, 1)


class _JaxprCoster:
    """One walk = one CostSummary accumulation (global mesh context).
    ``wire_dtype`` forces every collective's payload onto that wire
    (the what-if re-pricing knob); int8 what-if bytes are accumulated
    alongside the actual bytes either way."""

    def __init__(self, summary: CostSummary, axis_sizes: dict,
                 wire_dtype=None):
        self.s = summary
        self.axis_sizes = dict(axis_sizes or {})
        self.wire_dtype = wire_dtype
        # storage-aware operand bytes: a convert_element_type fuses into
        # its consumer's HBM read, so a matmul fed by convert(int8->bf16)
        # streams the int8 buffer, not a materialized bf16 copy — this
        # map remembers the narrower storage behind view/convert chains
        self._storage: dict = {}

    def _sbytes(self, v):
        """HBM bytes behind ``v``: its aval size, unless it is a fused
        view/convert of a narrower stored buffer."""
        return self._storage.get(id(v), _nbytes(v.aval))

    def charge(self, name, flops, nbytes, comm=0.0, comm_int8=None,
               eqn=None):
        self.s.flops += flops
        self.s.hbm_bytes += nbytes
        self.s.comm_bytes += comm
        self.s.comm_bytes_int8 += comm if comm_int8 is None else comm_int8
        rec = self.s.by_prim.setdefault(name, [0.0, 0.0, 0])
        rec[0] += flops
        rec[1] += nbytes
        rec[2] += 1
        if eqn is not None:
            site = self.s.by_site.setdefault(
                eqn_site_id(eqn), [0.0, 0.0, 0.0, 0, op_family(name)])
            site[0] += flops
            site[1] += nbytes
            site[2] += comm
            site[3] += 1

    # ------------------------------------------------------------------
    def walk(self, jaxpr, in_divs, mult=1.0):
        """Accumulate per-device cost of ``jaxpr``; ``in_divs`` maps each
        invar to the number of devices its data is partitioned over."""
        div = {}
        for v, d in zip(jaxpr.invars, in_divs):
            div[id(v)] = max(int(d or 1), 1)
        for v in jaxpr.constvars:
            div[id(v)] = 1

        def dof(v):
            if isinstance(v, jex_core.Literal):
                return 1
            return div.get(id(v), 1)

        # fusion model for HBM traffic: only materialized buffers stream.
        # An op that fuses (elementwise/reduce glue) charges bytes ONLY
        # for frame arguments it reads and frame outputs it writes —
        # those live in HBM no matter how XLA fuses (params read by the
        # optimizer update, updated state written back); everything else
        # it touches rides inside a consumer's fused loop for free.
        frame_in = {id(v) for v in jaxpr.invars}
        frame_in |= {id(v) for v in jaxpr.constvars}
        frame_out = {id(v) for v in jaxpr.outvars
                     if not isinstance(v, jex_core.Literal)}

        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            d_out = max([dof(v) for v in eqn.invars] or [1])
            for v in eqn.outvars:
                div[id(v)] = d_out

            # narrow-storage propagation: converts remember the stored
            # width they stream from; free view ops pass it through
            if name in ("convert_element_type",) or name in _FREE:
                ins = [v for v in eqn.invars
                       if not isinstance(v, jex_core.Literal)]
                if ins and eqn.outvars:
                    sb = min(self._sbytes(ins[0]),
                             _nbytes(eqn.outvars[0].aval))
                    if sb < _nbytes(eqn.outvars[0].aval):
                        self._storage[id(eqn.outvars[0])] = sb

            if name == "scan":
                body = eqn.params["jaxpr"].jaxpr
                length = int(eqn.params.get("length", 1) or 1)
                self.walk(body, [dof(v) for v in eqn.invars],
                          mult * length)
                continue
            if name == "while":
                body = eqn.params["body_jaxpr"].jaxpr
                nc = int(eqn.params.get("cond_nconsts", 0) or 0)
                self.walk(body, [dof(v) for v in eqn.invars[nc:]], mult)
                continue
            if name == "cond":
                branches = eqn.params["branches"]
                best = None
                for br in branches:
                    probe = CostSummary()
                    _JaxprCoster(probe, self.axis_sizes,
                                 self.wire_dtype).walk(
                        br.jaxpr, [dof(v) for v in eqn.invars[1:]], mult)
                    if best is None or probe.flops > best.flops:
                        best = probe
                if best is not None:
                    self.s.flops += best.flops
                    self.s.hbm_bytes += best.hbm_bytes
                    self.s.comm_bytes += best.comm_bytes
                    self.s.comm_bytes_int8 += best.comm_bytes_int8
                    for k, rec in best.by_prim.items():
                        acc = self.s.by_prim.setdefault(k, [0.0, 0.0, 0])
                        acc[0] += rec[0]
                        acc[1] += rec[1]
                        acc[2] += rec[2]
                    # only the winning branch's sites merge — the rows
                    # must add up to the charged totals, not both arms
                    for k, rec in best.by_site.items():
                        acc = self.s.by_site.setdefault(
                            k, [0.0, 0.0, 0.0, 0, rec[4]])
                        acc[0] += rec[0]
                        acc[1] += rec[1]
                        acc[2] += rec[2]
                        acc[3] += rec[3]
                continue
            if name == "shard_map":
                body = eqn.params["jaxpr"]
                mesh = eqn.params.get("mesh")
                sizes = dict(self.axis_sizes)
                if mesh is not None:
                    sizes.update({k: int(v)
                                  for k, v in dict(mesh.shape).items()})
                inner = _JaxprCoster(self.s, sizes, self.wire_dtype)
                # body shapes are already per-shard: divisor 1 throughout
                inner.walk(body, [1] * len(body.invars), mult)
                continue
            if name in _TRANSPARENT:
                subs = list(_sub_jaxprs(eqn.params))
                for sub in subs:
                    self.walk(sub, [dof(v) for v in eqn.invars], mult)
                continue

            if name == "pallas_call":
                # fused-kernel pricing: the body's FLOPs all execute
                # (once per grid step), but only the call's operands and
                # results stream HBM — every intermediate the body
                # touches lives in VMEM. This is what makes a fused
                # dispatch kernel cheaper than the identical unfused
                # math in the model, not just on the chip.
                probe = CostSummary()
                inner = _JaxprCoster(probe, self.axis_sizes,
                                     self.wire_dtype)
                for sub in _sub_jaxprs(eqn.params):
                    inner.walk(sub, [1] * len(sub.invars), 1.0)
                steps = 1
                gm = eqn.params.get("grid_mapping")
                for d in (getattr(gm, "grid", None) or ()):
                    if isinstance(d, int):
                        steps *= max(d, 1)
                self.charge(name, mult * probe.flops * steps / d_out,
                            mult * self._anchor_bytes(eqn) / d_out,
                            eqn=eqn)
                continue

            if name in _COLLECTIVES:
                axes = eqn.params.get("axes",
                                      eqn.params.get("axis_name"))
                n = _axis_size(axes, self.axis_sizes)
                # PER-OPERAND pricing: integer/bool operands are exact
                # by contract (the runtime refuses to compress them),
                # and an operand that is ALREADY int8 (a compressed
                # collective's own shards) cannot shrink further — each
                # operand compresses, or not, at its own width
                wire_payload = payload_i8 = 0.0
                for v in eqn.invars:
                    if isinstance(v, jex_core.Literal):
                        continue
                    b = _nbytes(v.aval)
                    dt = getattr(v.aval, "dtype", None)
                    fl = _floating_dtype(dt)
                    try:
                        ib = np.dtype(dt).itemsize
                    except TypeError:
                        ib = 4
                    wire_payload += _compressed_nbytes(
                        b, ib, self.wire_dtype) \
                        if self.wire_dtype and fl else b
                    payload_i8 += _compressed_nbytes(b, ib, "int8") \
                        if fl else b
                if n > 1:
                    wire = _COLLECTIVES[name](wire_payload, n)
                    wire_i8 = _COLLECTIVES[name](payload_i8, n)
                else:
                    wire = wire_i8 = 0.0
                # the reduction math itself: one FLOP per element per hop
                flops = float(sum(_nelems(v.aval) for v in eqn.invars
                                  if hasattr(v.aval, "shape")))
                self.charge(name, mult * flops / d_out, 0.0,
                            comm=mult * wire / d_out,
                            comm_int8=mult * wire_i8 / d_out, eqn=eqn)
                continue

            if name in _FREE:
                continue
            if name == "dynamic_update_slice":
                # work is the UPDATE operand, not the whole buffer a
                # one-flop-per-output-element default would charge (a
                # single-row write into a pool/cache is row-sized work)
                self.charge(name,
                            mult * _nelems(eqn.invars[1].aval) / d_out,
                            mult * self._anchor_bytes(eqn) / d_out,
                            eqn=eqn)
                continue
            if name == "dot_general":
                flops = _dot_general_flops(eqn)
                nbytes = self._anchor_bytes(eqn)
            elif name == "conv_general_dilated":
                flops = _conv_flops(eqn)
                nbytes = self._anchor_bytes(eqn)
            elif name in _FUSABLE:
                flops = _default_flops(eqn)
                nbytes = sum(_nbytes(v.aval) for v in eqn.invars
                             if not isinstance(v, jex_core.Literal)
                             and id(v) in frame_in)
                nbytes += sum(_nbytes(v.aval) for v in eqn.outvars
                              if id(v) in frame_out)
            else:
                subs = list(_sub_jaxprs(eqn.params))
                if subs:  # opaque higher-order prim (pallas_call, ...)
                    for sub in subs:
                        self.walk(sub, [1] * len(sub.invars), mult)
                    continue
                flops = _default_flops(eqn)
                nbytes = self._anchor_bytes(eqn)
            self.charge(name, mult * flops / d_out, mult * nbytes / d_out,
                        eqn=eqn)

    def _anchor_bytes(self, eqn):
        """HBM traffic of an op that materializes: stream inputs (at
        their STORED width — fused converts read the narrow buffer) +
        outputs."""
        nbytes = sum(self._sbytes(v) for v in eqn.invars
                     if not isinstance(v, jex_core.Literal))
        nbytes += sum(_nbytes(v.aval) for v in eqn.outvars)
        return float(nbytes)


def estimate_jaxpr_cost(closed_jaxpr, in_divisors=None, axis_sizes=None,
                        chip=None, wire_dtype=None) -> CostSummary:
    """Sharding-aware per-device FLOPs/bytes of one (Closed)Jaxpr, rolled
    into a roofline :class:`CostSummary`. ``in_divisors`` gives the
    device-partition count per top-level input (from PartitionSpecs via
    :func:`spec_divisor`); ``axis_sizes`` names the mesh axes collectives
    ring over; ``wire_dtype`` re-prices every collective at that wire
    (int8/bf16) — predicted wire-bytes reduction as a first-class
    output (``summary.comm_bytes`` vs an uncompressed run, or just read
    ``summary.int8_wire_reduction``)."""
    from ...observability.instrument import chip_specs
    jaxpr = (closed_jaxpr.jaxpr
             if isinstance(closed_jaxpr, jex_core.ClosedJaxpr)
             else closed_jaxpr)
    s = CostSummary()
    s.wire_dtype = wire_dtype
    divs = list(in_divisors or [])
    divs += [1] * (len(jaxpr.invars) - len(divs))
    _JaxprCoster(s, axis_sizes or {}, wire_dtype).walk(jaxpr, divs)
    return s.finalize(chip or chip_specs())


def site_rows(summary: CostSummary) -> list[dict]:
    """Per-site predicted roofline rows from a finalized cost walk: each
    call site priced by its OWN roofline (max of its compute/HBM/comm
    time on the summary's chip) with the dominating bound named. These
    are the prediction half of the op-attribution join
    (:mod:`paddle_tpu.observability.opprof`); per-site times do NOT sum
    to ``step_ms`` — the step roofline takes the max over totals, the
    rows answer *where* each resource's time goes."""
    chip = summary.chip or {}
    eff_peak = (float(chip.get("peak_flops") or 1.0)
                * float(chip.get("mxu_efficiency", MXU_EFFICIENCY)))
    hbm_bw = float(chip.get("hbm_bw") or 1.0)
    ici_bw = float(chip.get("ici_bw") or 1.0)
    rows = []
    for sid, (fl, hb, cm, n, fam) in sorted(summary.by_site.items()):
        compute_ms = 1e3 * fl / eff_peak
        hbm_ms = 1e3 * hb / hbm_bw
        comm_ms = 1e3 * cm / ici_bw
        ms = max(compute_ms, hbm_ms, comm_ms)
        bound = {compute_ms: "compute", hbm_ms: "memory",
                 comm_ms: "comm"}[ms]
        rows.append({"site": sid, "family": fam, "count": int(n),
                     "flops": fl, "hbm_bytes": hb, "comm_bytes": cm,
                     "predicted_ms": ms, "bound": bound})
    return rows


def spec_divisor(spec, mesh_shape: dict) -> int:
    """Number of devices a PartitionSpec splits an array over."""
    n = 1
    for part in tuple(spec or ()):
        if part is None:
            continue
        for ax in (part if isinstance(part, tuple) else (part,)):
            n *= int(mesh_shape.get(ax, 1))
    return max(n, 1)


def eager_collective_cost(ledger, world_size: int,
                          wire_dtype=None) -> float:
    """Wire bytes of the recorded eager collective schedule (rank 0's
    ledger), ring-modeled per device. Each record's own ``wire_dtype``
    (compressed groups) prices its compressed payload; ``wire_dtype=``
    forces the WHOLE schedule onto one wire — the what-if knob."""
    total = 0.0
    for rec in ledger or ():
        fn = _EAGER_COLLECTIVES.get(rec.op)
        if fn is None or rec.shape is None:
            continue
        try:
            itemsize = np.dtype(rec.dtype).itemsize
            nbytes = (int(np.prod(rec.shape, dtype=np.int64)) * itemsize)
        except (TypeError, ValueError):
            continue
        wire = wire_dtype or getattr(rec, "wire_dtype", None)
        if wire and _floating_dtype(rec.dtype):
            nbytes = _compressed_nbytes(nbytes, itemsize, wire)
        total += fn(nbytes, max(int(world_size), 1))
    return total


# ---------------------------------------------------------------------------
# PTCS004: unfused fusable chains (fusion opportunities, by kind)
# ---------------------------------------------------------------------------

# materializing glue the unfused dispatch streams through HBM between
# the gate and the expert matmul: position math, index gathers, token
# scatters, pad concats. All are cost-model ANCHORS (not in _FUSABLE),
# so the bytes counted here are exactly what the walk charged them.
_PTCS004_GLUE = {"cumsum", "gather", "scatter", "scatter-add",
                 "scatter_add", "sort", "concatenate",
                 "dynamic_update_slice"}
_PTCS004_FLOOR = 1 << 20   # toy traces (tests, tiny zoo configs) stay quiet
_PTCS004_RATIO = 2.0


def _moe_fusion_opportunities(jaxpr, _found=None, recurse=True):
    """Detect unfused gate→dispatch chains: a ``top_k`` (the routing
    decision) whose downstream dataflow materializes gather/scatter/
    cumsum glue charging > ``_PTCS004_RATIO``× the HBM traffic a fused
    dispatch kernel would stream (tokens read once + expert buffers
    written once — approximated by the chain's largest materialized
    output plus its largest input). Recurses into sub-jaxprs EXCEPT
    ``pallas_call`` bodies — a Pallas kernel is already the fused form.
    Returns ``[{glue_bytes, fused_bytes, n_ops, ratio, sites}, ...]``
    where ``sites`` are the glue eqns' :func:`eqn_site_id` keys — the
    join handles an op-attribution trace uses to attach MEASURED glue
    cost to each candidate (the ranked input auto-fusion needs)."""
    found = [] if _found is None else _found

    tainted = set()
    glue_bytes = 0.0
    big_out = 0.0
    big_in = 0.0
    n_ops = 0
    sites = []
    saw_topk = False
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "pallas_call":
            continue  # fused already; neither taints nor recurses
        if recurse:
            for sub in _sub_jaxprs(eqn.params):
                _moe_fusion_opportunities(sub, found)
        ins = [v for v in eqn.invars
               if not isinstance(v, jex_core.Literal)]
        hit = any(id(v) in tainted for v in ins)
        if name == "top_k":
            saw_topk = True
            hit = True
        if hit:
            for v in eqn.outvars:
                tainted.add(id(v))
            if name in _PTCS004_GLUE:
                n_ops += 1
                sid = eqn_site_id(eqn)
                if sid not in sites:
                    sites.append(sid)
                in_b = max([_nbytes(v.aval) for v in ins] or [0])
                out_b = max([_nbytes(v.aval) for v in eqn.outvars]
                            or [0])
                glue_bytes += sum(_nbytes(v.aval) for v in ins)
                glue_bytes += sum(_nbytes(v.aval) for v in eqn.outvars)
                if out_b > big_out:
                    big_out, big_in = out_b, in_b
    if saw_topk and n_ops:
        # what the fused kernel streams: the dispatched expert buffer
        # out + the token matrix in (the chain's dominant materialized
        # tensors), plus a small index/weight allowance
        fused = big_out + big_in + (64 << 10)
        if glue_bytes >= _PTCS004_FLOOR \
                and glue_bytes > _PTCS004_RATIO * fused:
            found.append({"kind": "moe_dispatch",
                          "glue_bytes": glue_bytes,
                          "fused_bytes": fused, "n_ops": n_ops,
                          "ratio": glue_bytes / fused, "sites": sites})
    return found


def _paged_gather_opportunities(jaxpr, _found=None, recurse=True):
    """Detect dense paged-KV gathers: rank-4 page-pool operands gathered
    whole-page (``slice_sizes == (1,) + pool.shape[1:]``) — the chunk
    prefill program's ``k_pages[page_table]`` materialization. The walk
    charges each such gather the full pool read plus the materialized
    dense copy (written, then re-read by the attention dots); the
    fused-kernel alternative streams only the touched pages, riding the
    page table on scalar prefetch (``ragged_prefill_attention``)."""
    found = [] if _found is None else _found
    glue_bytes = 0.0
    big_out = 0.0
    n_ops = 0
    sites = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "pallas_call":
            continue  # fused already
        if recurse:
            for sub in _sub_jaxprs(eqn.params):
                _paged_gather_opportunities(sub, found)
        if name != "gather":
            continue
        ins = [v for v in eqn.invars
               if not isinstance(v, jex_core.Literal)]
        if len(ins) != 2:
            continue
        op, idx = eqn.invars[0], eqn.invars[1]
        if getattr(op.aval, "ndim", 0) != 4 \
                or getattr(idx.aval, "ndim", 0) < 2:
            continue
        if np.dtype(idx.aval.dtype).kind not in "iu":
            continue
        ss = tuple(eqn.params.get("slice_sizes") or ())
        if ss != (1,) + tuple(op.aval.shape[1:]):
            continue
        n_ops += 1
        sid = eqn_site_id(eqn)
        if sid not in sites:
            sites.append(sid)
        out_b = max([_nbytes(v.aval) for v in eqn.outvars] or [0])
        glue_bytes += _nbytes(op.aval) + _nbytes(idx.aval) + 2 * out_b
        big_out = max(big_out, out_b)
    if n_ops:
        fused = big_out + (64 << 10)
        if glue_bytes >= _PTCS004_FLOOR \
                and glue_bytes > _PTCS004_RATIO * fused:
            found.append({"kind": "paged_attention",
                          "glue_bytes": glue_bytes,
                          "fused_bytes": fused, "n_ops": n_ops,
                          "ratio": glue_bytes / fused, "sites": sites})
    return found


def _dequant_matmul_opportunities(jaxpr, _found=None, recurse=True):
    """Detect unfused weight-only-int8 matmuls: ``convert(int8→float)``
    whose result feeds a ``dot_general`` (the engines' ``_mm`` dequant
    chain). The glue estimate is what an XLA backend without the
    narrow-storage fusion would materialize: the dequantized f32 weight
    (written + re-read) plus the pre-scale dot output round-trip; the
    fused kernel (``int8_matmul``) dequantizes in registers and writes
    the scaled result once."""
    found = [] if _found is None else _found
    cons: dict = {}
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        if recurse:
            for sub in _sub_jaxprs(eqn.params):
                _dequant_matmul_opportunities(sub, found)
        for v in eqn.invars:
            if not isinstance(v, jex_core.Literal):
                cons.setdefault(id(v), []).append(eqn)
    glue_bytes = 0.0
    big_out = 0.0
    n_ops = 0
    sites = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name != "convert_element_type":
            continue
        src = eqn.invars[0]
        if isinstance(src, jex_core.Literal) \
                or str(getattr(src.aval, "dtype", "")) != "int8":
            continue
        outv = eqn.outvars[0]
        if np.dtype(outv.aval.dtype).kind != "f":
            continue
        dots = [e for e in cons.get(id(outv), ())
                if e.primitive.name == "dot_general"]
        if not dots:
            continue
        n_ops += 1
        sid = eqn_site_id(dots[0])
        if sid not in sites:
            sites.append(sid)
        out_b = max([_nbytes(v.aval) for v in dots[0].outvars] or [0])
        glue_bytes += _nbytes(outv.aval) + 2 * out_b
        big_out = max(big_out, out_b)
    if n_ops:
        fused = big_out + (64 << 10)
        if glue_bytes >= _PTCS004_FLOOR \
                and glue_bytes > _PTCS004_RATIO * fused:
            found.append({"kind": "dequant_matmul",
                          "glue_bytes": glue_bytes,
                          "fused_bytes": fused, "n_ops": n_ops,
                          "ratio": glue_bytes / fused, "sites": sites})
    return found


def fusion_candidates(target, recurse=True):
    """Every PTCS004 fusion candidate in ``target`` (a ``Jaxpr`` or
    ``ClosedJaxpr``), all kinds pooled: ``moe_dispatch`` (gate→dispatch
    glue), ``paged_attention`` (dense paged-KV gathers),
    ``dequant_matmul`` (int8 dequant feeding a matmul). Each record is
    ``{kind, glue_bytes, fused_bytes, n_ops, ratio, sites}``; byte-sum
    descending (the heuristic ranking —
    :func:`ranked_fusion_candidates` upgrades to measured glue cost).
    ``recurse=False`` stays at this jaxpr level (the rewrite engine
    plans level by level)."""
    jaxpr = getattr(target, "jaxpr", target)
    found: list = []
    _moe_fusion_opportunities(jaxpr, found, recurse=recurse)
    _paged_gather_opportunities(jaxpr, found, recurse=recurse)
    _dequant_matmul_opportunities(jaxpr, found, recurse=recurse)
    found.sort(key=lambda c: -c["glue_bytes"])
    return found


def _env_attribution():
    """The op-attribution doc ``PADDLE_OP_ATTRIBUTION`` points at (a
    path to an ``op_attribution`` JSON), or None."""
    import json
    import os
    path = os.environ.get("PADDLE_OP_ATTRIBUTION")
    if not path or not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            doc = json.load(f)
        if doc.get("schema") == "op_attribution":
            return doc
    except (OSError, ValueError):
        pass
    return None


def ranked_fusion_candidates(target, attribution=None, recurse=True):
    """:func:`fusion_candidates`, ranked the way the auto-fusion rewrite
    should consume them: byte-count heuristics by default, upgraded to
    MEASURED glue cost (``attach_glue_cost``'s ``measured_glue_ms``,
    summed over each candidate's recorded sites) whenever an op
    attribution is present — passed in, or found via
    ``PADDLE_OP_ATTRIBUTION``. Chains that measurably burn wall-clock
    time sort first; byte-heavy-but-cheap chains stop jumping the
    queue."""
    cands = fusion_candidates(target, recurse=recurse)
    if attribution is None:
        attribution = _env_attribution()
    if attribution is None or not cands:
        return cands
    try:
        from ...observability import opprof
        attr = opprof.OpAttribution.from_dict(attribution) \
            if isinstance(attribution, dict) else attribution
        return opprof.attach_glue_cost(cands, attr)
    except Exception:
        return cands


# ---------------------------------------------------------------------------
# PTCS005: auto-fused kernels (the rewritten form of a PTCS004 chain)
# ---------------------------------------------------------------------------

# pallas_call names the auto-fusion rewrite templates stamp; programs
# containing them are the REWRITTEN form — PTCS004 goes quiet (the
# pallas_call skip above) and PTCS005 says which rule fired
_AUTOFUSE_KERNELS = {
    "autofuse_ragged_prefill": "ragged_prefill",
    "autofuse_int8_matmul": "int8_dequant_matmul",
    "autofuse_moe_gate_dispatch": "moe_gate_dispatch",
}


def _pallas_call_name(eqn) -> str:
    info = eqn.params.get("name_and_src_info")
    if info is not None:
        return str(info).split(" ")[0]
    return str(eqn.params.get("name") or "")


def autofused_sites(target, _found=None):
    """``[(site_id, rule, kernel_name), ...]`` for every auto-fusion
    template ``pallas_call`` in ``target`` — the PTCS005 join key."""
    jaxpr = getattr(target, "jaxpr", target)
    found = [] if _found is None else _found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            name = _pallas_call_name(eqn)
            rule = _AUTOFUSE_KERNELS.get(name)
            if rule is not None:
                found.append((eqn_site_id(eqn), rule, name))
            continue  # kernel bodies are opaque
        for sub in _sub_jaxprs(eqn.params):
            autofused_sites(sub, found)
    return found


# ---------------------------------------------------------------------------
# the registered pass
# ---------------------------------------------------------------------------

# a toy trace's AI is meaningless — only call a step memory-bound when it
# does real work
_PTCS002_FLOPS_FLOOR = 1e7
_PTCS001_COMM_FLOOR = 1 << 20  # 1 MiB on the wire


@register_pass("cost", order=60)
def cost_pass(ctx):
    ledger = ctx.ledgers.get(0) or []
    if ctx.jaxpr is None and not ledger:
        return []
    from ...observability.instrument import chip_specs
    chip = getattr(ctx, "chip", None) or chip_specs()
    axis_sizes = dict(getattr(ctx, "axis_sizes", None) or {})
    s = CostSummary()
    if ctx.jaxpr is not None:
        divs = list(getattr(ctx, "in_divisors", None) or [])
        jaxpr = ctx.jaxpr.jaxpr
        divs += [1] * (len(jaxpr.invars) - len(divs))
        _JaxprCoster(s, axis_sizes).walk(jaxpr, divs)
    s.comm_bytes += eager_collective_cost(ledger, ctx.world_size)
    s.comm_bytes_int8 += eager_collective_cost(ledger, ctx.world_size,
                                               wire_dtype="int8")
    s.finalize(chip)
    ctx.cost_summary = s

    out = []
    if (s.bound == "comm" and s.comm_bytes >= _PTCS001_COMM_FLOOR
            and s.comm_ms > 0):
        whatif = {
            "comm_bytes_int8": s.comm_bytes_int8,
            "comm_ms_int8": round(s.comm_ms_int8, 4),
            "wire_reduction": round(s.int8_wire_reduction, 3),
            "bound_if_int8": s.bound_if_int8,
        }
        out.append(Diagnostic(
            "PTCS001", "cost", "warning",
            f"comm-bound step: predicted interconnect time "
            f"{s.comm_ms:.3f} ms exceeds compute ({s.compute_ms:.3f} ms) "
            f"and HBM ({s.hbm_ms:.3f} ms) on {chip.get('name')} — "
            f"{s.comm_bytes / 2 ** 20:.1f} MiB/device on the wire per "
            f"step (ring model); re-shard to cut collective payloads, "
            f"overlap them with compute, or compress the wire (what-if: "
            f"int8 cuts wire bytes {s.int8_wire_reduction:.2f}x to "
            f"{s.comm_ms_int8:.3f} ms -> {s.bound_if_int8}-bound)",
            extra={"cost": s.as_dict(), "whatif_int8": whatif}))
        if s.bound_if_int8 != "comm":
            out.append(Diagnostic(
                "PTCS003", "cost", "info",
                f"compression would flip the bound: int8-compressed "
                f"collectives (new_group(compress='int8') / "
                f"prims.c_*_q) cut predicted comm time "
                f"{s.comm_ms:.3f} -> {s.comm_ms_int8:.3f} ms, making "
                f"the step {s.bound_if_int8}-bound "
                f"({s.int8_wire_reduction:.2f}x fewer wire bytes); "
                f"distributed.auto_enable_compression(report) turns "
                f"this on",
                extra={"whatif_int8": whatif}))
    elif (s.flops >= _PTCS002_FLOPS_FLOOR and s.hbm_bytes > 0
            and s.bound == "memory" and s.arithmetic_intensity < s.ridge):
        out.append(Diagnostic(
            "PTCS002", "cost", "info",
            f"low arithmetic intensity: "
            f"{s.arithmetic_intensity:.1f} FLOPs/HBM-byte vs the "
            f"{chip.get('name')} ridge point {s.ridge:.0f} — the step is "
            f"memory-bound at {s.predicted_mfu:.1%} predicted MFU; fuse "
            f"elementwise chains, grow the batch, or store in bf16",
            extra={"cost": s.as_dict()}))
    if ctx.jaxpr is not None:
        _KIND_MSG = {
            "moe_dispatch": (
                "an unfused gate→dispatch chain (top-k routing + {n} "
                "materialized gather/scatter/cumsum ops)",
                "tokens in + expert buffers out",
                "kernels.moe_dispatch.fused_moe_dispatch / "
                "MoELayer(fused_dispatch=True) is the fused path"),
            "paged_attention": (
                "a dense paged-KV gather ({n} whole-page gather(s) "
                "materializing the page pool per step)",
                "touched pages streamed via scalar prefetch",
                "kernels.paged_attention.ragged_prefill_attention is "
                "the fused path"),
            "dequant_matmul": (
                "an unfused int8 dequant-matmul ({n} "
                "convert(int8)→dot chain(s) materializing the "
                "dequantized weight)",
                "int8 weight in + scaled result out",
                "kernels.int8_matmul.int8_matmul is the fused path"),
        }
        for opp in ranked_fusion_candidates(ctx.jaxpr.jaxpr):
            what, fused_what, fix = _KIND_MSG[opp["kind"]]
            measured = opp.get("measured_glue_ms")
            rank_note = (f" (measured glue: {measured:.3f} ms — ranked "
                         f"by attributed wall-clock)"
                         if measured is not None else "")
            out.append(Diagnostic(
                "PTCS004", "cost", "info",
                f"fusion opportunity: {what.format(n=opp['n_ops'])} "
                f"streams {opp['glue_bytes'] / 2 ** 20:.1f} MiB of HBM "
                f"glue — {opp['ratio']:.1f}x what a fused kernel would "
                f"move (~{opp['fused_bytes'] / 2 ** 20:.1f} MiB: "
                f"{fused_what}){rank_note}. {fix}; the "
                f"analysis.rewrite auto-fusion pass applies it "
                f"automatically",
                extra={"fusion": {k: round(v, 1) if isinstance(v, float)
                                  else v for k, v in opp.items()}}))
        for site, rule, kernel in autofused_sites(ctx.jaxpr.jaxpr):
            delta = None
            try:
                from ..rewrite import fired_delta
                delta = fired_delta(rule)
            except Exception:
                pass
            dtxt = (f"predicted Δstep {delta:+.3f} ms vs the unfused "
                    f"chain" if isinstance(delta, (int, float))
                    else "predicted Δstep not recorded in this process")
            out.append(Diagnostic(
                "PTCS005", "cost", "info",
                f"auto-fused: rule '{rule}' rewrote this program's "
                f"glue chain into the {kernel} Pallas kernel at {site} "
                f"({dtxt}); the fused form is what the walk priced — "
                f"PADDLE_NO_AUTOFUSE=1 restores the unfused program",
                extra={"autofusion": {"site": site, "rule": rule,
                                      "kernel": kernel,
                                      "predicted_delta_ms": delta}}))
    return out
