"""CPU rehearsals of the benchmark: run by hand before any chip call
(``python -m pytest benchmark/tests -q``), not collected by the repo's
tier-1 run. Every cell's code path goes end to end through ``run.py`` at
``gpt_tiny_config`` widths, from a test-only manifest made of nothing but
data files (``manifest.json``, ``configs/``, ``traffic/``, ``limits/``
beside this file): which is also the proof that a cell is added by adding
files and one ``workloads`` entry.
"""
import io
import json
import os
import re
import subprocess
import sys
import time
from contextlib import redirect_stdout

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)


def drive(cell, trace=0, seed=(1 << 31) + 11, seconds=1.5, **steering):
    """One run through ``run.main`` with the tests' steering; the parsed
    last line of its standard output."""
    import run
    from harness.core import Steer
    steer = Steer(manifest=os.path.join(HERE, "manifest.json"), root=HERE,
                  allow_cpu=True, **steering)
    out = io.StringIO()
    with redirect_stdout(out):
        run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                  str(seconds), "--trace", str(trace)], steer=steer,
                 t_start=time.perf_counter())
    return json.loads(out.getvalue().strip().splitlines()[-1])


def check_contract(line, manifest_group, cell):
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "compared"
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    manifest = json.load(open(os.path.join(HERE, "manifest.json")))
    names = {m["name"] for m in manifest[manifest_group]
             if cell in m.get("workloads", [cell])}
    assert set(line["metrics"]) <= names
    for metric in line["metrics"].values():
        assert set(metric) == {"value", "unit"}
    for entry in line["compared"].values():
        assert set(entry) == {"value", "limit"}


# ---- every cell's path, end to end ---------------------------------------

@pytest.mark.parametrize("cell", ["tiny-train", "tiny-train-dp2mp2",
                                  "tiny-chat"])
def test_cell_end_to_end(cell):
    line = drive(cell)
    check_contract(line, "end_to_end", cell)
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert "setup_s" in line["metrics"] and len(line["metrics"]) >= 2


@pytest.mark.parametrize("cell", ["tiny-train", "tiny-train-dp2mp2",
                                  "tiny-chat"])
def test_cell_traced(cell):
    line = drive(cell, trace=1)
    check_contract(line, "per_layer", cell)
    assert line["correct"] is True, line["compared"]
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"] * 4
    assert len(line["breakdown"]["device_ops"]) <= 10
    assert len(line["breakdown"]["idle_gaps"]) <= 10
    # a share of a peak or of a roofline has nothing to read off the chip
    assert not [m for m in line["metrics"] if "mfu" in m or "roofline" in m]


def test_same_seed_same_inputs():
    from drivers.train import Feed
    from harness import traffic
    a, b = Feed(7, 4, 16, 256).next(), Feed(7, 4, 16, 256).next()
    assert (a[0] == b[0]).all() and len({tuple(r) for r in a[0]}) == 4
    mix = json.load(open(os.path.join(BENCH, "traffic",
                                      "chat-steady.json")))
    sizes = lambda rs: [(d, len(p), o) for d, p, o in rs]
    inside = lambda rs: [r for r in rs if 12.0 <= r[0] < 57.0]
    one = traffic.requests(mix, 5, 12.0, 45.0, 117.0, 50304)
    two = traffic.requests(mix, 5, 12.0, 45.0, 117.0, 50304)
    other = traffic.requests(mix, (1 << 31) + 6, 12.0, 45.0, 117.0, 50304)
    assert sizes(one) == sizes(two)
    assert all((p == q).all() for (_, p, _), (_, q, _) in zip(one, two))
    # another seed: the same set of gaps and sizes, lead-in and window
    # apart, in another order, and other tokens
    assert sizes(one) != sizes(other)
    lead_in = lambda rs: [r for r in rs if r[0] < 12.0]
    for part, end in ((inside, 57.0), (lead_in, 12.0)):
        a, b = part(one), part(other)
        assert sorted((len(p), o) for _, p, o in a) == \
            sorted((len(p), o) for _, p, o in b)
        gaps = lambda rs: np.sort(np.diff([d for d, _, _ in rs] + [end]))
        assert np.allclose(gaps(a), gaps(b))
    rate = mix["arrivals"]["rate_per_s"]
    assert len(inside(one)) == len(inside(other)) == round(rate * 45.0)
    lens = np.array([len(p) for _, p, _ in one])
    outs = np.array([o for _, _, o in one])
    assert lens.min() >= 32 and lens.max() <= 1536
    assert outs.min() >= 8 and outs.max() <= 384
    assert (lens + outs).max() <= 2048


# ---- the control and the planted faults come out as not correct ---------

def _state_unchanged(step):
    """A step that returns its state unchanged."""
    import jax
    import jax.numpy as jnp
    step._build()
    compiled = step._compiled

    def frozen(params, opt_state, *rest):
        kept = jax.tree.map(jnp.copy, (params, opt_state))   # donated
        loss, _, _ = compiled(params, opt_state, *rest)
        return (loss,) + kept
    step._compiled = frozen
    return step


def _half_batch(step):
    """Half of the batch left out, the mean taken over the rest."""
    call = step.__class__.__call__

    class Halved(step.__class__):
        def __call__(self, ids, labels):
            return call(self, ids[:len(ids) // 2], labels[:len(labels) // 2])
    step.__class__ = Halved
    return step


def _no_exchange(step):
    """The exchange between chips left out: the sums over the mp ranks
    that ``gpt_block`` takes after its row-parallel products."""
    import jax
    psum = jax.lax.psum
    jax.lax.psum = lambda x, axis_name, **kw: x
    try:
        step.lower_step(4, 64)      # traces the step with the sums gone
    finally:
        jax.lax.psum = psum
    return step


def _altered_token(engine):
    """A token altered where it is produced."""
    decode = engine.decode
    calls = []

    def altered(seq_ids, bucket=None):
        out = decode(seq_ids, bucket)
        calls.append(1)
        if len(calls) % 5 == 0:
            out[0] = (out[0] + 97) % 256
        return out
    engine.decode = altered
    return engine


@pytest.mark.parametrize("cell,fault", [
    ("tiny-train", _state_unchanged),
    ("tiny-train", _half_batch),
    ("tiny-train-dp2mp2", _no_exchange),
    ("tiny-chat", _altered_token),
])
def test_planted_fault_is_not_correct(cell, fault):
    line = drive(cell, break_program=fault)
    assert line["correct"] is False, line["compared"]


@pytest.mark.parametrize("cell,number,times", [
    ("tiny-train", "change_gap", 3), ("tiny-chat", "logit_gap", 2)])
def test_control_is_not_correct(cell, number, times):
    """The lower precision in the program's place, through the harness's
    own comparison: the program's bfloat16 masters and moments for
    training; for serving the float8 reference, whose first token at
    every position of the served prompts and tokens is the one compared."""
    line = drive(cell, lower_precision=True)
    assert line["correct"] is False, line["compared"]
    assert line["compared"][number]["value"] > \
        times * line["compared"][number]["limit"]


def test_bfloat16_reference_is_within_the_limit():
    """bfloat16 is the served precision, not one below it: the reference
    computed so keeps within the tiny cell's limit where float8 does not."""
    from models import gpt
    cfg = gpt.load_config(os.path.join(HERE, "configs", "gpt-tiny.json"))
    limit = json.load(open(os.path.join(
        HERE, "limits", "tiny-chat.json")))["limits"]["logit_gap"]
    for seed in range(3):
        weights = gpt.init_weights(cfg, seed)
        rng = np.random.default_rng(seed)
        prompt, served = rng.integers(0, 256, 8), rng.integers(0, 256, 119)
        gap = {mode: gpt.served_token_gaps(cfg, weights, prompt, served,
                                           128, mode=mode).max()
               for mode in ("bf16", gpt.SERVING_CONTROL)}
        assert gap["bf16"] < limit < gap[gpt.SERVING_CONTROL], (seed, gap)


# ---- the trace reducer against the recorded trace ------------------------

def test_trace_reducer_on_recorded_trace():
    from harness import trace
    path = os.path.join(BENCH, "harness", "recorded.xplane.pb")
    want = json.load(open(os.path.join(BENCH, "harness",
                                       "recorded.expect.json")))
    summary = trace.summarize(trace.load(path))
    assert summary["window_s"] == pytest.approx(want["window_s"])
    assert summary["busy_s"] == pytest.approx(want["busy_s"])
    assert 0 < summary["busy_s"] <= summary["window_s"]
    assert [k for k, _ in summary["device_ops"][:3]] == want["top3"]
    assert {k for k, _ in summary["idle_gaps"]} <= set(want["gap_spans"])


def test_self_time_and_union():
    from harness import trace
    events = [(0, 100, "while.1"), (10, 30, "fusion.1"), (40, 90, "dot.2"),
              (120, 130, "all-reduce.3")]
    own = trace.self_times(events, 0, 200)
    assert own["while.1"][0] == 30 and own["dot.2"][0] == 50
    assert trace.merged(events, 0, 125) == [(0, 100), (120, 125)]
    assert trace.op_kind("fusion.12") == "fusion"
    assert trace.is_collective("all-reduce-start.4")
    assert trace.is_collective(
        "%psum.5 = bf16[4,2048,2048]{2,1,0:T(8,128)(2,1)} all-reduce("
        "bf16[4,2048,2048]{2,1,0:T(8,128)(2,1)} %fusion.3), channel_id=1")
    assert not trace.is_collective(
        "%fusion.7 = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(bf16[8,128]{1,0} "
        "%all-reduce.3), kind=kLoop, calls=%fused_computation.7")


# ---- the harness refuses what it must ------------------------------------

def test_no_result_without_a_chip_or_the_steering():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "train-345m-1chip", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=REPO, env=env, capture_output=True, text=True,
        timeout=300)
    assert done.returncode != 0
    assert "correct" not in done.stdout


def test_manifest_is_within_the_contract():
    m = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    cells = [c["name"] for c in m["workloads"]]
    e2e = {x["name"]: x for x in m["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    assert sum(c["chips"] == 4 for c in m["workloads"]) <= max(
        1, len(cells) // 4)
    for c in m["workloads"]:
        assert name.match(c["name"]) and len(c["why"]) <= 200
        for kind, key in (("traffic", "traffic"), ("limits", "name")):
            assert os.path.exists(os.path.join(BENCH, kind,
                                               c[key] + ".json"))
    for c in m["configs"]:
        assert os.path.exists(os.path.join(REPO, c["file"]))
        assert c["reduced"] == json.load(
            open(os.path.join(REPO, c["file"])))["reduced"]
    for x in m["end_to_end"] + m["per_layer"]:
        assert name.match(x["name"]) and x["better"] in ("lower", "higher")
        assert set(x.get("workloads", cells)) <= set(cells)
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           x["name"] + ".py"))
    for x in m["end_to_end"]:
        assert 0.01 <= x["bound"] <= 0.1
        assert x["source"] in ("host_clock", "device_trace")
    for x in m["per_layer"]:
        assert x["moves"] in e2e
        for cell in x.get("workloads", cells):
            assert cell in e2e[x["moves"]].get("workloads", cells)
    for cell in cells:       # setup_s, another end-to-end, one per-layer
        assert sum(cell in x.get("workloads", cells)
                   for x in m["end_to_end"]) >= 2
        assert any(cell in x.get("workloads", cells)
                   for x in m["per_layer"])
