"""Op-level profile↔prediction attribution.

The cost model prices every eqn; the profiler measures every step. This
module joins the two at **op granularity**: each cost-walk call site
(``analysis.passes.cost.eqn_site_id`` — ``file.py:L123:prim``) gets a
measured time next to its predicted roofline time, so when a whole-step
prediction is wrong we can say *which op family* is wrong, and PTCS004
fusion candidates can be ranked by their MEASURED glue cost.

Three pieces:

- **site tagging** (:func:`tag_sites`): re-evaluates a jaxpr with every
  eqn wrapped in ``jax.named_scope(<site id>)``. Jitted on a real chip,
  the scope names land in the XLA op metadata, so ``jax.profiler``
  traces carry the join key and :func:`ingest_profiler_trace` can read
  measured per-site times straight out of the chrome trace.
- **CPU replay harness** (:func:`replay_attribution`): an instrumented
  eqn-by-eqn jaxpr interpreter that times each ``primitive.bind``
  individually — no real chip needed, so the whole attribution pipeline
  (tag → measure → join → calibrate → doctor) runs in tier-1.
- **the join** (:class:`OpAttribution`): per-site rows
  ``{measured_ms, predicted_ms, flops, hbm_bytes, bound, rel_err}``
  whose measured times **sum exactly to the measured step total** — the
  interpreter/tooling overhead is booked as an explicit
  ``unattributed`` row, same contract as the perf doctor's residual
  bucket (the residual is a bucket, not an apology).

:func:`drift_findings` turns an attribution into PTCM001 cost-model
drift findings (+ the ``paddle_cost_model_drift_ratio{family}`` gauge)
when a family's measured/predicted ratio leaves the stated band; the
doctor surfaces them next to its step-time buckets, and
:mod:`.calibration` fits correction constants from the same rows.

Module import is stdlib-only (jax is imported inside the functions that
trace or execute), so the doctor and the offline tools can load
attribution files and compute drift anywhere.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
import time
from dataclasses import dataclass, field

# a family whose measured/predicted time ratio leaves this band raises
# PTCM001 — inside it, disagreement is treated as noise, not drift
DRIFT_BAND = (0.5, 2.0)
# families below this measured time are too small to diagnose drift on
DRIFT_MIN_MS = 0.05

UNATTRIBUTED = "unattributed"
SCHEMA = "op_attribution"

_SCOPE_SAFE = re.compile(r"[^A-Za-z0-9_.:\-]")


def _scope_name(site_id: str) -> str:
    """``jax.named_scope``-safe spelling of a site id (the raw id stays
    the table key; the scope name is what lands in trace metadata)."""
    return _SCOPE_SAFE.sub("_", site_id)


# ---------------------------------------------------------------------------
# the attribution table
# ---------------------------------------------------------------------------

@dataclass
class OpAttribution:
    """Measured-vs-predicted join at op-site granularity.

    ``rows`` hold one dict per site — ``site, family, count,
    measured_ms, predicted_ms, flops, hbm_bytes, bound, rel_err`` — plus
    exactly one ``unattributed`` residual row; their ``measured_ms``
    sum to ``measured_total_ms`` exactly (float addition of the very
    numbers in the table, not a re-measurement)."""

    rows: list = field(default_factory=list)
    measured_total_ms: float = 0.0
    chip: str | None = None
    calibration_id: str = "default"
    source: str = "replay"          # replay | jax_profiler
    fusion_candidates: list = field(default_factory=list)

    def as_dict(self) -> dict:
        return {
            "schema": SCHEMA,
            "chip": self.chip,
            "calibration_id": self.calibration_id,
            "source": self.source,
            "measured_total_ms": self.measured_total_ms,
            "rows": self.rows,
            "fusion_candidates": self.fusion_candidates,
        }

    @classmethod
    def from_dict(cls, doc: dict) -> "OpAttribution":
        return cls(
            rows=list(doc.get("rows") or ()),
            measured_total_ms=float(doc.get("measured_total_ms") or 0.0),
            chip=doc.get("chip"),
            calibration_id=doc.get("calibration_id", "default"),
            source=doc.get("source", "replay"),
            fusion_candidates=list(doc.get("fusion_candidates") or ()),
        )

    def save(self, path: str) -> str:
        dirname = os.path.dirname(path)
        if dirname:
            os.makedirs(dirname, exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.as_dict(), f, indent=1, sort_keys=True)
        return path

    @classmethod
    def load(cls, path: str) -> "OpAttribution":
        with open(path) as f:
            return cls.from_dict(json.load(f))

    # -- views ------------------------------------------------------------

    def sum_check(self) -> tuple[float, float]:
        """(sum of row measured_ms, measured_total_ms) — equal by the
        table's construction; the doctor re-asserts it on load."""
        return (sum(float(r.get("measured_ms") or 0.0) for r in self.rows),
                self.measured_total_ms)

    def by_family(self) -> dict:
        """family -> {measured_ms, predicted_ms, ratio, rows} over the
        attributed rows (the residual keeps its own bucket)."""
        out: dict[str, dict] = {}
        for r in self.rows:
            fam = r.get("family") or "other"
            agg = out.setdefault(fam, {"measured_ms": 0.0,
                                       "predicted_ms": 0.0, "rows": 0})
            agg["measured_ms"] += float(r.get("measured_ms") or 0.0)
            agg["predicted_ms"] += float(r.get("predicted_ms") or 0.0)
            agg["rows"] += 1
        for agg in out.values():
            agg["measured_ms"] = round(agg["measured_ms"], 6)
            agg["predicted_ms"] = round(agg["predicted_ms"], 6)
            agg["ratio"] = (
                round(agg["measured_ms"] / agg["predicted_ms"], 4)
                if agg["predicted_ms"] > 0 else None)
        return out

    def top_deviations(self, n: int = 10) -> list:
        """The n attributed sites with the largest absolute
        measured-minus-predicted gap — the doctor's ``--ops`` table."""
        attributed = [r for r in self.rows
                      if r.get("family") != UNATTRIBUTED]
        return sorted(
            attributed,
            key=lambda r: abs(float(r.get("measured_ms") or 0.0)
                              - float(r.get("predicted_ms") or 0.0)),
            reverse=True)[:n]


# ---------------------------------------------------------------------------
# jaxpr interpreters: site tagging + the timed CPU replay
# ---------------------------------------------------------------------------

def _inner_jaxpr(eqn):
    """(jaxpr, consts) of a transparent call-like eqn the interpreters
    descend into — matching the cost walk, so site ids line up."""
    name = eqn.primitive.name
    if name in ("jit", "closed_call", "custom_jvp_call",
                "custom_vjp_call", "remat2", "checkpoint", "remat"):
        inner = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
        if hasattr(inner, "jaxpr"):          # ClosedJaxpr
            return inner.jaxpr, list(inner.consts)
        if inner is not None:                # raw Jaxpr (remat2)
            return inner, []
    return None


def _zeros_like_aval(aval):
    import jax.numpy as jnp
    try:
        return jnp.zeros(aval.shape, aval.dtype)
    except (AttributeError, TypeError):
        return None


def _run_jaxpr(jaxpr, consts, args, timings=None):
    """Evaluate ``jaxpr`` eqn by eqn, each bind inside
    ``jax.named_scope(<site id>)``.

    With ``timings`` (a dict) this is the **replay harness**: each bind
    is individually timed (``block_until_ready`` closes the async
    window) and accumulated under its site id — including on a failed
    bind, so the table still sums (the eqn's outputs degrade to zeros
    and downstream eqns keep executing). Without ``timings`` it is the
    **tagging pass**: pure re-evaluation, safe to trace/jit, leaving
    the scope names in the lowered program's op metadata."""
    import jax
    from jax.extend import core
    env: dict = {}

    def read(v):
        return v.val if isinstance(v, core.Literal) else env.get(v)

    def write(v, val):
        env[v] = val

    for v, c in zip(jaxpr.constvars, consts):
        write(v, c)
    for v, a in zip(jaxpr.invars, args):
        write(v, a)
    for eqn in jaxpr.eqns:
        invals = [read(v) for v in eqn.invars]
        inner = _inner_jaxpr(eqn)
        if inner is not None and len(inner[0].invars) == len(invals):
            outs = _run_jaxpr(inner[0], inner[1], invals, timings)
            for v, val in zip(eqn.outvars, outs):
                write(v, val)
            continue
        from ..analysis.passes.cost import eqn_site_id
        sid = eqn_site_id(eqn)
        if timings is None:
            with jax.named_scope(_scope_name(sid)):
                outs = eqn.primitive.bind(*invals, **eqn.params)
        else:
            t0 = time.perf_counter()
            try:
                with jax.named_scope(_scope_name(sid)):
                    outs = eqn.primitive.bind(*invals, **eqn.params)
                jax.block_until_ready(outs)
            except Exception:
                # keep replaying: zeros of the right shape downstream,
                # and the time spent failing still lands on this site
                outs = [_zeros_like_aval(v.aval) for v in eqn.outvars]
                if not eqn.primitive.multiple_results:
                    outs = outs[0]
            finally:
                timings[sid] = timings.get(sid, 0.0) + \
                    (time.perf_counter() - t0)
        if eqn.primitive.multiple_results:
            for v, val in zip(eqn.outvars, outs):
                write(v, val)
        else:
            write(eqn.outvars[0], outs)
    return [read(v) for v in jaxpr.outvars]


def tag_sites(closed_jaxpr):
    """A callable re-evaluating ``closed_jaxpr`` with every eqn inside
    its site-id named scope. ``jax.jit(tag_sites(cj))`` on a real chip
    emits the scopes into op metadata, so a ``jax.profiler`` trace of
    the jitted call carries the attribution join key."""
    jaxpr = closed_jaxpr.jaxpr
    consts = list(closed_jaxpr.consts)

    def tagged(*args):
        outs = _run_jaxpr(jaxpr, consts, list(args), timings=None)
        return outs[0] if len(outs) == 1 else tuple(outs)

    return tagged


def _synth_args(closed_jaxpr):
    return [_zeros_like_aval(v.aval) for v in closed_jaxpr.jaxpr.invars]


# ---------------------------------------------------------------------------
# the join
# ---------------------------------------------------------------------------

def _join(measured_ms_by_site, total_ms, predicted_rows, chip_name,
          calibration=None, source="replay", fusion_candidates=None):
    """Assemble the OpAttribution table: one row per site seen on
    either side, family corrections applied to predictions, and the
    residual (total minus the attributed sum) booked as the
    ``unattributed`` row so the table sums exactly to ``total_ms``."""
    corr = (calibration or {}).get("family_correction") or {}
    pred_by_site = {r["site"]: r for r in predicted_rows}
    rows = []
    attributed = 0.0
    for sid in sorted(set(measured_ms_by_site) | set(pred_by_site)):
        p = pred_by_site.get(sid, {})
        fam = p.get("family") or "other"
        predicted = float(p.get("predicted_ms") or 0.0) \
            * float(corr.get(fam, 1.0))
        measured = float(measured_ms_by_site.get(sid, 0.0))
        attributed += measured
        rows.append({
            "site": sid, "family": fam,
            "count": int(p.get("count") or 0),
            "measured_ms": measured, "predicted_ms": predicted,
            "flops": float(p.get("flops") or 0.0),
            "hbm_bytes": float(p.get("hbm_bytes") or 0.0),
            "bound": p.get("bound"),
            "rel_err": ((measured - predicted) / predicted
                        if predicted > 0 else None),
        })
    rows.append({
        "site": UNATTRIBUTED, "family": UNATTRIBUTED, "count": 0,
        "measured_ms": total_ms - attributed, "predicted_ms": 0.0,
        "flops": 0.0, "hbm_bytes": 0.0, "bound": None, "rel_err": None,
    })
    attr = OpAttribution(
        rows=rows, measured_total_ms=total_ms, chip=chip_name,
        calibration_id=(calibration or {}).get("calibration_id",
                                               "default"),
        source=source)
    if fusion_candidates:
        attr.fusion_candidates = attach_glue_cost(fusion_candidates, attr)
    return attr


def attach_glue_cost(candidates, attribution) -> list:
    """PTCS004 fusion candidates with ``measured_glue_ms`` attached —
    the sum of measured time over the candidate's recorded glue
    ``sites``. This is the ranked input auto-fusion needs: candidates
    whose glue actually costs wall-clock time first."""
    measured = {r["site"]: float(r.get("measured_ms") or 0.0)
                for r in attribution.rows}
    out = []
    for cand in candidates or ():
        cand = dict(cand)
        sites = cand.get("sites") or ()
        hit = [s for s in sites if s in measured]
        if hit:
            cand["measured_glue_ms"] = round(
                sum(measured[s] for s in hit), 6)
        out.append(cand)
    return sorted(out, key=lambda c: -(c.get("measured_glue_ms") or 0.0))


def replay_attribution(target, args=None, chip=None, calibration=None,
                       fusion_candidates=None) -> OpAttribution:
    """Attribution via the CPU replay harness.

    ``target`` is a ClosedJaxpr, or a callable traced against ``args``.
    One untimed warmup replay fills dispatch caches, then the timed
    replay runs eqn by eqn; predictions come from the cost walk's
    per-site export on the same jaxpr, priced on ``chip`` (default: the
    attached device's specs, calibration applied). The measured rows +
    the ``unattributed`` residual sum exactly to the measured total."""
    import jax
    from ..analysis.passes.cost import estimate_jaxpr_cost, site_rows
    from .instrument import chip_specs
    from .calibration import active_calibration

    if hasattr(target, "jaxpr"):
        closed = target
    else:
        closed = jax.make_jaxpr(target)(*(args or ()))
    replay_args = _synth_args(closed) if args is None else list(args)
    if calibration is None:
        calibration = active_calibration()
    spec = chip or chip_specs()

    summary = estimate_jaxpr_cost(closed, chip=spec)
    predicted = site_rows(summary)

    jaxpr, consts = closed.jaxpr, list(closed.consts)
    _run_jaxpr(jaxpr, consts, replay_args, timings={})  # warmup
    timings: dict[str, float] = {}
    t0 = time.perf_counter()
    _run_jaxpr(jaxpr, consts, replay_args, timings=timings)
    total_ms = (time.perf_counter() - t0) * 1e3
    measured = {sid: s * 1e3 for sid, s in timings.items()}
    return _join(measured, total_ms, predicted,
                 spec.get("name"), calibration=calibration,
                 source="replay", fusion_candidates=fusion_candidates)


# ---------------------------------------------------------------------------
# real-chip ingestion: jax.profiler chrome traces
# ---------------------------------------------------------------------------

def _iter_trace_events(path: str):
    """Events of one chrome trace file (.json / .json.gz), or of the
    newest ``*.trace.json.gz`` under a ``jax.profiler`` log dir."""
    if os.path.isdir(path):
        cands = sorted(
            glob.glob(os.path.join(path, "**", "*.trace.json*"),
                      recursive=True) +
            glob.glob(os.path.join(path, "**", "trace.json*"),
                      recursive=True))
        if not cands:
            return []
        path = cands[-1]
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        doc = json.load(f)
    events = doc.get("traceEvents", doc) if isinstance(doc, dict) else doc
    return [e for e in events if isinstance(e, dict)]


def ingest_profiler_trace(trace_path, target_or_rows, chip=None,
                          calibration=None, total_ms=None,
                          fusion_candidates=None) -> OpAttribution:
    """Attribution from a real ``jax.profiler`` trace of a
    :func:`tag_sites`-wrapped program. Spans whose names carry a site's
    scope name are summed per site; the measured total is ``total_ms``
    when given, else the trace's wall extent — everything the spans
    don't cover lands in ``unattributed``, keeping the sum contract.

    ``target_or_rows``: the ClosedJaxpr (re-priced here) or the cost
    walk's ``site_rows`` list, so ingestion itself never needs a
    device."""
    if isinstance(target_or_rows, (list, tuple)):
        predicted = list(target_or_rows)
        chip_name = (chip or {}).get("name") if isinstance(chip, dict) \
            else chip
    else:
        from ..analysis.passes.cost import estimate_jaxpr_cost, site_rows
        from .instrument import chip_specs
        spec = chip if isinstance(chip, dict) else chip_specs(chip)
        predicted = site_rows(estimate_jaxpr_cost(target_or_rows,
                                                  chip=spec))
        chip_name = spec.get("name")

    by_scope = {_scope_name(r["site"]): r["site"] for r in predicted}
    measured: dict[str, float] = {}
    t_min = t_max = None
    for ev in _iter_trace_events(trace_path):
        if ev.get("ph") != "X":
            continue
        ts, dur = float(ev.get("ts") or 0.0), float(ev.get("dur") or 0.0)
        t_min = ts if t_min is None else min(t_min, ts)
        t_max = (ts + dur) if t_max is None else max(t_max, ts + dur)
        name = str(ev.get("name") or "")
        for scope, sid in by_scope.items():
            if scope in name:
                measured[sid] = measured.get(sid, 0.0) + dur / 1e3
                break
    if total_ms is None:
        total_ms = ((t_max - t_min) / 1e3
                    if t_min is not None else
                    sum(measured.values()))
    return _join(measured, float(total_ms), predicted, chip_name,
                 calibration=calibration, source="jax_profiler",
                 fusion_candidates=fusion_candidates)


# ---------------------------------------------------------------------------
# PTCM001: cost-model drift
# ---------------------------------------------------------------------------

def drift_findings(attribution, band=DRIFT_BAND, min_ms=DRIFT_MIN_MS,
                   publish=True) -> list:
    """PTCM001 findings from an attribution (object or its dict form):
    one warning per op family whose measured/predicted ratio leaves
    ``band`` with at least ``min_ms`` of measured time behind it. Every
    family with a finite ratio also lands on the
    ``paddle_cost_model_drift_ratio{family}`` gauge (``publish=False``
    for pure-JSON consumers like the doctor's file path)."""
    if isinstance(attribution, dict):
        attribution = OpAttribution.from_dict(attribution)
    lo, hi = band
    findings = []
    for fam, agg in sorted(attribution.by_family().items()):
        if fam == UNATTRIBUTED or agg.get("ratio") is None:
            continue
        ratio = agg["ratio"]
        if publish:
            from .instrument import cost_model_drift_gauge
            cost_model_drift_gauge().set(float(ratio), family=fam)
        if agg["measured_ms"] < min_ms:
            continue
        if lo <= ratio <= hi:
            continue
        direction = "slower" if ratio > hi else "faster"
        findings.append({
            "code": "PTCM001",
            "severity": "warning",
            "message": (
                f"cost-model drift: family '{fam}' measured "
                f"{agg['measured_ms']:.3f}ms vs predicted "
                f"{agg['predicted_ms']:.3f}ms (ratio {ratio:.2f}, "
                f"band [{lo}, {hi}]) — hardware is {direction} than "
                f"the model; refit with observability.calibration"),
            "family": fam,
            "ratio": ratio,
            "band": [lo, hi],
            "measured_ms": agg["measured_ms"],
            "predicted_ms": agg["predicted_ms"],
        })
    return findings
