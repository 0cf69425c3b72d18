"""AMP cast audit.

- **PTAM001** (warning) — an fp16-unsafe op (the AMP black list:
  softmax, log, norms, losses...) reached with a float16 input and no
  black-list upcast active: overflows/underflows at fp16's 65504 range.
  (bfloat16 shares float32's exponent range, so it is exempt.) Read from
  the tape's op records, which see pre-promotion dtypes and the cast the
  AMP state actually applied.
- **PTAM002** (warning) — a redundant up/down-cast pair in the jaxpr:
  ``convert_element_type`` through a WIDER dtype directly feeding a
  convert back to the original with no other consumer — value-identical
  to dropping both casts, so the advice is always semantics-preserving
  (down-up pairs through a narrower dtype are quantize-dequantize and
  deliberately NOT flagged; an intermediate that is itself a program
  output is exempt too).
"""
from __future__ import annotations

from collections import defaultdict

import jax
import jax.extend.core as jex_core

from ..core import Diagnostic, register_pass
from ..tracing import eqn_site


@register_pass("amp", order=40)
def amp_pass(ctx):
    out = []
    _fp16_unsafe(ctx, out)
    _redundant_casts(ctx, out)
    return out


def _fp16_unsafe(ctx, out):
    from ...amp.auto_cast import BLACK_LIST
    seen = set()
    for rec in ctx.op_records:
        if rec.name not in BLACK_LIST or rec.amp_mode == "black":
            continue
        if not any(kind == "T" and dt == "float16"
                   for kind, dt, _ in rec.ins):
            continue
        key = (rec.name, rec.file, rec.line)
        if key in seen:
            continue
        seen.add(key)
        out.append(Diagnostic(
            "PTAM001", "amp", "warning",
            f"fp16-unsafe op '{rec.name}' (AMP black list) reached with "
            f"a float16 input and no up-cast: fp16's 5-bit exponent "
            f"overflows at 65504 (softmax/log/norm territory) — run "
            f"under amp.auto_cast (which black-lists this op to f32), "
            f"or use bfloat16",
            op=rec.name, file=rec.file, line=rec.line))


def _redundant_casts(ctx, out):
    if ctx.jaxpr is None:
        return
    producer = {}       # var id -> producing convert eqn
    uses = defaultdict(int)
    out_ids = set()     # vars that are (sub)jaxpr outputs — not droppable
    convert_eqns = []
    for jx in _iter_jaxprs(ctx.jaxpr):
        out_ids.update(id(v) for v in jx.outvars
                       if not isinstance(v, jex_core.Literal))
        for eqn in jx.eqns:
            for v in eqn.invars:
                if not isinstance(v, jex_core.Literal):
                    uses[id(v)] += 1
            if eqn.primitive.name == "convert_element_type":
                convert_eqns.append(eqn)
                producer[id(eqn.outvars[0])] = eqn
    seen = set()
    for eqn in convert_eqns:
        src = eqn.invars[0]
        if isinstance(src, jex_core.Literal):
            continue
        up = producer.get(id(src))
        if up is None or uses[id(src)] != 1 or id(src) in out_ids:
            continue
        orig_dtype = up.invars[0].aval.dtype
        if eqn.outvars[0].aval.dtype != orig_dtype:
            continue
        mid_dtype = src.aval.dtype
        # only WIDENING middles (f16→f32→f16): value-identical to no
        # casts at all, so "drop both" is always safe advice. A narrower
        # middle (f32→f16→f32) is quantize-dequantize — intentional in
        # QAT/fake-quant code — and must not be flagged.
        try:
            if jax.numpy.finfo(mid_dtype).bits <= \
                    jax.numpy.finfo(orig_dtype).bits:
                continue
        except ValueError:  # integer middles: compare item sizes
            if jax.numpy.dtype(mid_dtype).itemsize <= \
                    jax.numpy.dtype(orig_dtype).itemsize:
                continue
        file, line = eqn_site(eqn)
        key = (str(orig_dtype), str(mid_dtype), file, line)
        if key in seen:
            continue
        seen.add(key)
        out.append(Diagnostic(
            "PTAM002", "amp", "warning",
            f"redundant cast pair: {orig_dtype} → {mid_dtype} → "
            f"{orig_dtype} with no op in between — value-identical to "
            f"no cast, two wasted HBM round trips; drop both casts",
            op="cast", file=file, line=line))


def _iter_jaxprs(jaxpr):
    """Every (sub)Jaxpr reachable from a ClosedJaxpr, top first."""
    if isinstance(jaxpr, jex_core.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    stack = [jaxpr]
    while stack:
        jx = stack.pop()
        yield jx
        for eqn in jx.eqns:
            for v in eqn.params.values():
                stack.extend(_sub_jaxprs_of(v))


def _sub_jaxprs_of(v):
    if isinstance(v, jex_core.ClosedJaxpr):
        return [v.jaxpr]
    if isinstance(v, jex_core.Jaxpr):
        return [v]
    if isinstance(v, (list, tuple)):
        out = []
        for x in v:
            out.extend(_sub_jaxprs_of(x))
        return out
    return []
