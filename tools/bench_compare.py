"""Diff two ``BENCH_rNN.json`` artifacts, anchored on predicted rows.

The driver's bench rounds run in a container whose CPU allotment varies
~40% run to run, so raw measured deltas are mostly noise. Two row
classes therefore get different treatment:

- ``*_predicted`` rows come from the static cost model: **zero run-to-run
  noise**, so ANY worsening beyond a tight threshold (default 2%) is a
  real modelled regression — the code got slower/bigger, not the box.
- measured rows use a wide threshold (default 40%, the observed
  container variance); additionally, when a measured row has a matching
  predicted anchor (``gpt_345m_tokens_per_sec_per_chip`` ↔
  ``gpt_345m_predicted``), the report shows the anchor-normalized ratio
  (measured / predicted), the number that SHOULD be environment-stable.

Rows whose unit marks them non-metrics (skipped / error / timeout /
info) are ignored, as are ``*_cpu_smoke`` vs TPU mismatches (a CPU
fallback round never regresses a TPU number).

Every row carries a ``calibration_id`` in its extras (hash of the
active ``calibration.json``, or ``"default"``). A measured row is only
anchor-normalized against a predicted row produced under the SAME
calibration — a refit changes what "predicted" means, so crossing ids
would book the calibration delta as an environment drift. Refused
anchors are reported per-row (``anchor_refused``), never silently
dropped.

Exit codes: 0 = no regressions, 1 = regression(s) beyond threshold,
2 = artifact unreadable.

Usage::

    python tools/bench_compare.py BENCH_r03.json BENCH_r06.json
    python tools/bench_compare.py A.json B.json --threshold 0.3 --json
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_NON_METRIC_UNITS = {"skipped", "error", "timeout", "info"}
# metrics where a LOWER value is the improvement
_LOWER_IS_BETTER_MARKERS = ("decode_ms", "peak_hbm", "step_ms", "latency")


def load_rows(path) -> dict:
    """``{metric: row}`` from one driver artifact (``tail`` lines +
    ``parsed``) or from a bare JSONL of bench rows. Later lines win."""
    rows = {}
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, dict) and ("tail" in doc or "parsed" in doc):
        lines = str(doc.get("tail", "")).splitlines()
        if isinstance(doc.get("parsed"), dict):
            lines.append(json.dumps(doc["parsed"]))
    elif isinstance(doc, list):
        lines = [json.dumps(r) for r in doc]
    else:
        lines = [json.dumps(doc)]
    for ln in lines:
        ln = ln.strip()
        if not (ln.startswith("{") and '"metric"' in ln):
            continue
        try:
            rec = json.loads(ln)
        except ValueError:
            continue
        metric = rec.get("metric")
        if not isinstance(metric, str):
            continue
        if str(rec.get("unit", "")).lower() in _NON_METRIC_UNITS:
            continue
        if metric.endswith(("_SKIPPED", "_ERROR", "_TIMEOUT", "_FALLBACK")):
            continue
        if not isinstance(rec.get("value"), (int, float)) \
                or rec["value"] <= 0:
            continue
        rows[metric] = rec
    return rows


def _lower_is_better(metric, row):
    u = str(row.get("unit", "")).lower()
    return any(m in metric for m in _LOWER_IS_BETTER_MARKERS) \
        or u.startswith(("ms", "gib", "gb", "s/"))


# measured metric -> its predicted anchor, where the suffix rule below
# doesn't apply (serving + quantized-collective rows)
_ANCHOR_MAP = {
    "serving_engine_tokens_per_sec": "serving_predicted",
    "serving_engine_int8_tokens_per_sec": "serving_int8_predicted",
    "serving_shared_prefix": "serving_shared_prefix_predicted",
    "serving_disagg": "serving_disagg_predicted",
    # the MoE serving engine row (ERNIE-MoE, fused Pallas dispatch)
    # anchors on the static cost model's MoE decode-program row
    # the N-replica fleet row anchors on the fleet roofline model
    # (per-replica roofline x N minus router overhead)
    "serving_fleet_tokens_per_sec": "serving_fleet_predicted",
    "serving_fleet": "serving_fleet_predicted",
    # a future measured live-migration row (ms per moved request /
    # resume speedup) anchors on the payload-over-interconnect model
    "serving_fleet_migration": "serving_fleet_migration_predicted",
    "serving_fleet_migration_ms": "serving_fleet_migration_predicted",
    # the overload-control A/B (deadline-met goodput at 2x-capacity
    # arrival) anchors on the control-vs-FIFO roofline model
    "serving_overload": "serving_overload_predicted",
    "serving_overload_goodput_tokens_per_sec":
        "serving_overload_predicted",
    "collective_compression": "collective_compression_predicted",
    # future measured auto-fusion rows (per-rule step-ms saved on TPU)
    # anchor on the rewrite pass's predicted per-rule Δstep-ms rows
    "autofusion": "autofusion_predicted",
    "autofusion_ms_saved": "autofusion_predicted",
    "autofusion_int8_dequant_matmul":
        "autofusion_int8_dequant_matmul_predicted",
    "autofusion_ragged_prefill": "autofusion_ragged_prefill_predicted",
    "autofusion_moe_gate_dispatch":
        "autofusion_moe_gate_dispatch_predicted",
    # a measured planner-config 13B run (TPU rounds) anchors on the
    # planner's own predicted row, not the hand-written config's
    "gpt_13b_planned_tokens_per_sec_per_chip": "gpt_13b_planned_predicted",
}


def _calibration_of(row) -> str:
    """The calibration id a row was produced under. Rows predate the
    stamp or were emitted with no calibration active → "default"."""
    extras = row.get("extras") or {}
    return str(extras.get("calibration_id")
               or row.get("calibration_id") or "default")


def _predicted_anchor(metric, rows):
    """The *_predicted row anchoring a measured metric, if present
    (gpt_345m_tokens_per_sec_per_chip -> gpt_345m_predicted;
    serving/collective rows via the explicit map)."""
    base = metric[:-len("_cpu_smoke")] if metric.endswith("_cpu_smoke") \
        else metric
    if base in _ANCHOR_MAP:
        return rows.get(_ANCHOR_MAP[base])
    for cut in ("_tokens_per_sec_per_chip", "_imgs_per_sec_per_chip"):
        if metric.endswith(cut):
            return rows.get(metric[: -len(cut)] + "_predicted")
    return None


def compare(rows_a: dict, rows_b: dict, threshold=0.40,
            predicted_threshold=0.02) -> dict:
    """Per-metric deltas + regression verdicts between two row maps."""
    out = {"metrics": [], "regressions": [], "only_a": [], "only_b": []}
    out["only_a"] = sorted(set(rows_a) - set(rows_b))
    out["only_b"] = sorted(set(rows_b) - set(rows_a))
    for metric in sorted(set(rows_a) & set(rows_b)):
        a, b = rows_a[metric], rows_b[metric]
        va, vb = float(a["value"]), float(b["value"])
        change = (vb - va) / va
        predicted = metric.endswith("_predicted") or "_predicted_" in metric
        lower_better = _lower_is_better(metric, b)
        worsening = change > 0 if lower_better else change < 0
        limit = predicted_threshold if predicted else threshold
        regression = worsening and abs(change) > limit
        rec = {
            "metric": metric, "a": va, "b": vb,
            "change_pct": round(100 * change, 2),
            "predicted": predicted, "lower_is_better": lower_better,
            "regression": regression, "threshold_pct": round(100 * limit, 1),
        }
        anchor_a = _predicted_anchor(metric, rows_a)
        anchor_b = _predicted_anchor(metric, rows_b)
        if anchor_a and anchor_b and not predicted:
            mismatch = [
                f"{side} measured={_calibration_of(row)} "
                f"anchor={_calibration_of(anchor)}"
                for side, row, anchor in (("A", a, anchor_a),
                                          ("B", b, anchor_b))
                if _calibration_of(row) != _calibration_of(anchor)]
            if mismatch:
                # predicted constants differ from the ones active when
                # the measurement ran — the ratio would mix a refit into
                # the environment story; refuse, visibly
                rec["anchor_refused"] = ("calibration mismatch: "
                                         + "; ".join(mismatch))
            else:
                # measured/predicted: the environment-independent view —
                # predicted rows absorb intentional model/config changes
                na = va / float(anchor_a["value"])
                nb = vb / float(anchor_b["value"])
                rec["anchored_ratio_a"] = round(na, 4)
                rec["anchored_ratio_b"] = round(nb, 4)
                rec["anchored_change_pct"] = round(100 * (nb - na) / na, 2)
        out["metrics"].append(rec)
        if regression:
            out["regressions"].append(rec)
    return out


def format_table(result) -> str:
    lines = [f"{'metric':<46} {'A':>12} {'B':>12} {'Δ%':>8}  verdict"]
    lines.append("-" * len(lines[0]))
    for rec in result["metrics"]:
        verdict = "REGRESSION" if rec["regression"] else (
            "anchor" if rec["predicted"] else "ok")
        extra = ""
        if "anchored_change_pct" in rec:
            extra = f"  (vs-predicted {rec['anchored_change_pct']:+.1f}%)"
        elif "anchor_refused" in rec:
            extra = f"  (anchor refused: {rec['anchor_refused']})"
        lines.append(
            f"{rec['metric']:<46} {rec['a']:>12.1f} {rec['b']:>12.1f} "
            f"{rec['change_pct']:>+7.1f}%  {verdict}{extra}")
    for side, label in (("only_a", "only in A"), ("only_b", "only in B")):
        for m in result[side]:
            lines.append(f"{m:<46} {label}")
    n = len(result["regressions"])
    lines.append(f"{n} regression(s) beyond threshold"
                 if n else "no regressions beyond threshold")
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="diff two bench artifacts; predicted rows are "
                    "noise-free anchors, exit 1 on regression")
    ap.add_argument("artifact_a", help="older BENCH_rNN.json")
    ap.add_argument("artifact_b", help="newer BENCH_rNN.json")
    ap.add_argument("--threshold", type=float, default=0.40,
                    help="measured-row regression threshold (fraction; "
                         "default 0.40 ≈ container CPU variance)")
    ap.add_argument("--predicted-threshold", type=float, default=0.02,
                    help="predicted-row regression threshold (fraction)")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)
    try:
        rows_a, rows_b = load_rows(args.artifact_a), load_rows(args.artifact_b)
    except (OSError, ValueError) as e:
        print(f"bench_compare: cannot read artifact: {e}", file=sys.stderr)
        return 2
    result = compare(rows_a, rows_b, threshold=args.threshold,
                     predicted_threshold=args.predicted_threshold)
    if args.json:
        print(json.dumps(result, indent=2, sort_keys=True))
    else:
        print(format_table(result))
    return 1 if result["regressions"] else 0


if __name__ == "__main__":
    sys.exit(main())
