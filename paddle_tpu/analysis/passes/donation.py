"""Buffer-donation sanitizer.

Donation (``jax.jit(donate_argnums=...)``) is what makes the train-step
hot path zero-copy: params and optimizer state alias in-place across
steps. Its failure modes are silent or deferred-fatal, so they get
static diagnostics:

- **PTBD001** (error) — use-after-donate: an input a jitted call donates
  is read again afterwards (a later eqn, or escaping as an output of the
  enclosing trace). At runtime that buffer is deleted the moment the
  call dispatches — the read crashes with jax's opaque "donated buffer
  was deleted" *sometimes*, and on other backends silently reads stale
  memory.
- **PTBD002** (warning) — donated-but-never-aliased: a donated input has
  no output of matching shape/dtype to alias onto, so XLA silently drops
  the donation — the zero-copy promise is a no-op and the buffer is
  wasted HBM for the whole call.
- **PTBD003** (warning) — donatable-but-not-donated: a fleet train step
  built with ``donate=False`` carries params + optimizer state through
  every call by copy — double HBM for the largest arrays on the hot
  path. (ParallelTrainStep donates by default; this fires only when the
  debugging escape hatch is left on.)
"""
from __future__ import annotations

import jax
import jax.extend.core as jex_core

from ..core import Diagnostic, register_pass
from ..tracing import eqn_site
from .cost import _nbytes, _sub_jaxprs


def _iter_jaxprs(jaxpr):
    if isinstance(jaxpr, jex_core.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    stack = [jaxpr]
    while stack:
        jx = stack.pop()
        yield jx
        for eqn in jx.eqns:
            stack.extend(_sub_jaxprs(eqn.params))


@register_pass("donation", order=70)
def donation_pass(ctx):
    out = []
    if ctx.jaxpr is not None:
        _pjit_donation_audit(ctx, out)
    _train_step_donation(ctx, out)
    return out


def _pjit_donation_audit(ctx, out):
    """Walk every (sub)jaxpr for ``jit`` eqns (the primitive JAX 0.9
    binds for a jitted call) that donate, and check each
    donated operand's fate in the ENCLOSING frame."""
    for jx in _iter_jaxprs(ctx.jaxpr):
        out_ids = {id(v) for v in jx.outvars
                   if not isinstance(v, jex_core.Literal)}
        for i, eqn in enumerate(jx.eqns):
            if eqn.primitive.name != "jit":
                continue
            donated = eqn.params.get("donated_invars") or ()
            if not any(donated):
                continue
            name = eqn.params.get("name") or "<jit fn>"
            # which outputs can alias each donated input (XLA matches by
            # shape+dtype; each output aliases at most one input)
            free_outs = [v.aval for v in eqn.outvars
                         if not isinstance(v, jax.core.DropVar)]
            for pos, (v, don) in enumerate(zip(eqn.invars, donated)):
                if not don or isinstance(v, jex_core.Literal):
                    continue
                used_later = any(
                    any(id(u) == id(v) for u in later.invars
                        if not isinstance(u, jex_core.Literal))
                    for later in jx.eqns[i + 1:])
                escapes = id(v) in out_ids
                if used_later or escapes:
                    file, line = eqn_site(eqn)
                    how = ("read by a later op" if used_later
                           else "returned from the traced function")
                    out.append(Diagnostic(
                        "PTBD001", "donation", "error",
                        f"use-after-donate: argument {pos} of jitted "
                        f"'{name}' is donated (its buffer is deleted at "
                        f"dispatch) but is {how} — at runtime this "
                        f"crashes with 'donated buffer was deleted' or "
                        f"silently reads freed memory; pass a copy or "
                        f"drop it from donate_argnums",
                        op=name, file=file, line=line,
                        extra={"arg_index": pos}))
                    continue
                aval = v.aval
                match = next(
                    (j for j, o in enumerate(free_outs)
                     if o.shape == aval.shape and o.dtype == aval.dtype),
                    None)
                if match is None:
                    file, line = eqn_site(eqn)
                    out.append(Diagnostic(
                        "PTBD002", "donation", "warning",
                        f"donated-but-never-aliased: argument {pos} of "
                        f"jitted '{name}' ({aval.dtype}"
                        f"{list(aval.shape)}, "
                        f"{_nbytes(aval) / 2 ** 20:.1f} MiB) has no "
                        f"output of matching shape/dtype — XLA silently "
                        f"disables the donation, so the aliasing you "
                        f"asked for never happens; return an updated "
                        f"value of the same shape/dtype or stop "
                        f"donating it",
                        op=name, file=file, line=line,
                        extra={"arg_index": pos}))
                else:
                    free_outs.pop(match)


def _train_step_donation(ctx, out):
    """PTBD003: a fleet train step explicitly built with donate=False
    re-copies params + optimizer state every call."""
    step = getattr(ctx, "train_step", None)
    if step is None or getattr(step, "donate", True):
        return
    nbytes = 0
    try:
        for p in getattr(step, "_params", []) or []:
            v = getattr(p, "_value", None)
            if v is not None:
                nbytes += _nbytes(v)
    except Exception:
        nbytes = 0
    mib = nbytes / 2 ** 20
    # Adam-family state is ~2x the params on top of the params themselves
    out.append(Diagnostic(
        "PTBD003", "donation", "warning",
        f"donatable-but-not-donated: this train step was built with "
        f"donate=False, so params ({mib:.1f} MiB) and optimizer state "
        f"(~{2 * mib:.1f} MiB for Adam) are copied on every step instead "
        f"of aliasing in place — double HBM residency and an extra "
        f"device-to-device copy on the hot path; drop donate=False "
        f"outside debugging",
        op=type(step).__name__,
        extra={"params_mib": round(mib, 1)}))
