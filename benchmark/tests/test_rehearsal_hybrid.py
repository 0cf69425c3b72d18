"""CPU rehearsals of the hybrid serving cell, beside ``test_rehearsal.py``
and ``test_rehearsal_blocks.py`` (run by hand: ``python -m pytest
benchmark/tests -q``). A PR that adds a cell may not edit the files the
benchmark has, so the ``tiny-hybrid`` cell stands in a rehearsal manifest
of its own (``manifest_hybrid.json``), made of data files like the
others: ``configs/phi4flash-tiny.json``, ``traffic/tiny-hybrid.json``,
``limits/tiny-hybrid.json``.
"""
import io
import json
import os
import time
from contextlib import redirect_stdout

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
MANIFEST = os.path.join(HERE, "manifest_hybrid.json")
CELL = "serve-phi4flash-reasoning-steady"


def drive(cell, trace=0, seed=(1 << 31) + 23, seconds=1.5, **steering):
    import run
    from harness.core import Steer
    steer = Steer(manifest=MANIFEST, root=HERE, allow_cpu=True, **steering)
    out = io.StringIO()
    with redirect_stdout(out):
        record = run.execute(
            ["--workload", cell, "--seed", str(seed), "--seconds",
             str(seconds), "--trace", str(trace)], steer=steer,
            t_start=time.perf_counter())
    return json.loads(out.getvalue().strip().splitlines()[-1]), record


def _tiny():
    from models import phi4flash
    return phi4flash, phi4flash.load_config(
        os.path.join(HERE, "configs", "phi4flash-tiny.json"))


def test_cell_end_to_end():
    line, record = drive("tiny-hybrid")
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"itl_p90_ms", "setup_s"}
    assert set(line["compared"]) == {"logit_gap", "never_finished",
                                     "compiles_in_window"}
    assert record.counters["checked_tokens"] > 0
    assert record.counters["ticks"] and record.counters["chunks"]


def test_cell_traced():
    line, record = drive("tiny-hybrid", trace=1)
    assert line["correct"] is True, line["compared"]
    names = {m["name"] for m in json.load(open(MANIFEST))["per_layer"]}
    assert set(line["metrics"]) <= names
    for name in ("state_slots_peak_share.serve",
                 "cache_bytes_per_token.serve", "decode_host_ms.serve",
                 "decode_wait_ms.serve", "sched_self_ms.serve",
                 "decode_tick_ms.serve", "pool_peak_share.serve"):
        assert name in line["metrics"], name
    share = line["metrics"]["state_slots_peak_share.serve"]["value"]
    assert 0 < share <= 100
    # a slot of the tiny model is 8 x the cache of one of its tokens: a
    # live token costs its 128 B of pages and its share of a slot
    assert line["metrics"]["cache_bytes_per_token.serve"]["value"] > 128
    # a share of a peak or of a roofline has nothing to read off the chip
    assert not [m for m in line["metrics"] if "mfu" in m or "roofline" in m]
    from harness import core
    kinds = record.model.layer_kinds(record.config)
    for kernel, calls in (("shared_kv_decode.py", 1 + kinds.count("cross")),
                          ("window_decode.py", kinds.count("window")),
                          ("selective_scan_chunk.py", kinds.count("mamba"))):
        needs = core.load_module(os.path.join(
            BENCH, "kernels", kernel)).needs(record)
        (each,) = needs.values()
        assert each and len(each) % calls == 0
        assert all(f > 0 and b > 0 for f, b in each)


def test_control_is_not_correct():
    """The float8 reference in the program's place, through the harness's
    own comparison."""
    line, _ = drive("tiny-hybrid", lower_precision=True)
    assert line["correct"] is False, line["compared"]
    gap = line["compared"]["logit_gap"]
    assert gap["value"] > 1.5 * gap["limit"]


@pytest.fixture(scope="module")
def served():
    """Prompts and served tokens drawn at random: a stand-in is held
    against the sound reference at every position of both, so any
    context will do."""
    phi, cfg = _tiny()
    rng = np.random.default_rng(5)
    reqs = [(rng.integers(0, cfg["vocab_size"], p),
             rng.integers(0, cfg["vocab_size"], n))
            for p, n in ((10, 9), (27, 14), (41, 20))]
    return phi, cfg, phi.whole_weights(cfg, 5), reqs


def test_sound_reference_holds_every_served_token(served):
    phi, cfg, layers, reqs = served
    for prompt, tokens in reqs:
        gaps = phi.served_token_gaps(cfg, {"seed": 5}, prompt, tokens, 0,
                                     layers=layers)
        assert len(gaps) == len(tokens) and float(gaps.min()) >= 0
        ids = np.concatenate([prompt, tokens])
        logits = np.asarray(phi.reference_logits(cfg, layers, ids))
        at = np.arange(len(prompt) - 1, len(ids) - 1)
        want = logits[at].max(-1) - logits[at, tokens]
        assert np.abs(gaps - want).max() < 1e-6


@pytest.mark.parametrize("stand_in", ["fp8", "chunk_state_zeroed",
                                      "slot_not_reset", "window_ignored",
                                      "cross_stale", "memory_after_gate",
                                      "no_lambda"])
def test_stand_in_in_the_programs_place(served, stand_in):
    """The control and each fault planted in the reference (what
    ``hybrid_controls.py --what faults`` does on the chip at the published
    size). Each moves the logits; all but a row of staleness and a state
    lost at a chunk boundary also move a first token past the tiny
    cell's limit."""
    phi, cfg, layers, reqs = served
    kw = {"mode": stand_in} if stand_in == phi.SERVING_CONTROL \
        else {"fault": stand_in}
    assert stand_in in phi.FAULTS + (phi.SERVING_CONTROL,)
    limit = json.load(open(os.path.join(
        HERE, "limits", "tiny-hybrid.json")))["limits"]["logit_gap"]
    prompt, tokens = reqs[-1]
    ids = np.concatenate([prompt, tokens])
    moved = np.abs(np.asarray(phi.reference_logits(cfg, layers, ids, **kw))
                   - np.asarray(phi.reference_logits(cfg, layers, ids)))
    assert moved.max() > 0.1, (stand_in, moved.max())
    if stand_in not in ("cross_stale", "chunk_state_zeroed"):
        worst = max(float(phi.served_token_gaps(
            cfg, {"seed": 5}, p, t, 0, layers=layers, **kw).max())
            for p, t in reqs)
        assert worst > limit, (stand_in, worst)


def test_weights_by_layer_are_the_stacked_weights():
    phi, cfg = _tiny()
    seed = (1 << 31) + 7
    whole = phi.init_weights(cfg, seed, dtype="float32")
    assert phi.init_weights(cfg, seed) == {"seed": seed}
    kinds = phi.layer_kinds(cfg)
    where = {0: ("self_pairs", "mamba", 0), 3: ("self_pairs", "attn", 1),
             6: ("l16",), 7: ("l17",), 8: ("cross_pairs", "gmu", 0),
             11: ("cross_pairs", "cross", 1)}
    for layer, path in where.items():
        kind, leaves = phi.layer_weights(cfg, seed, layer)
        assert kind == kinds[layer]
        tree = whole[path[0]] if len(path) == 1 else whole[path[0]][path[1]]
        for k, v in leaves.items():
            got = tree[k] if len(path) == 1 else tree[k][path[2]]
            assert (np.asarray(got) == np.asarray(v)).all(), (layer, k)
    assert abs(float(np.asarray(whole["l17"]["ln1_w"]).mean()) - 1) < 0.05


def test_operation_counts_at_the_published_widths():
    phi, _ = _tiny()
    cfg = phi.load_config(os.path.join(
        BENCH, "configs", "phi-4-mini-flash-reasoning.json"))
    assert abs(phi.param_count(cfg) - 3853e6) < 2e6
    self_mm = phi.param_count(cfg, ("mamba", "window", "full"))
    assert abs(self_mm - 1964e6) < 2e6
    # the reader's one list: two chunks' positions, then two ticks' lengths
    ctx = list(range(1, 257)) + list(range(257, 300)) + [310, 900] + [311]
    assert phi._prompt_runs(ctx, 256) == 299
    whole = phi.serve_flops(cfg, ctx)
    assert whole == phi.serve_flops(cfg, ctx, prompt_positions=299)
    produced = phi.serve_flops(cfg, [310, 900, 311], prompt_positions=0)
    prompt = phi.serve_flops(cfg, ctx[:299], prompt_positions=299)
    assert abs(whole - produced - prompt) < 1e-6 * whole
    # a produced token: every layer and the head, eight readers of its
    # context; a prompt position: the self-decoder
    assert 2 * 3853e6 < produced / 3 < 2 * 3853e6 * 1.05
    assert 2 * 1964e6 < prompt / 299 < 2 * 1964e6 * 1.05


def test_manifest_additions_are_within_the_contract():
    m = json.load(open(os.path.join(os.path.dirname(BENCH),
                                    "BENCHMARK.json")))
    cell = {c["name"]: c for c in m["workloads"]}[CELL]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert sum(c["chips"] == 4 for c in m["workloads"]) == 1
    cfg = json.load(open(os.path.join(
        BENCH, "configs", "phi-4-mini-flash-reasoning.json")))
    entry = {c["name"]: c for c in m["configs"]}[cell["config"]]
    assert cfg["reduced"] == [] == entry["reduced"]
    assert entry["source"] == cfg["source"] and len(entry["why"]) <= 200
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = next(r for r in map(json.loads, open(catalog))
                   if r["name"] == "Phi-4-mini-flash-reasoning")
        assert row["source_url"] == cfg["source"]
        assert {k: cfg[k] for k in row["config"]} == row["config"]
    mix = json.load(open(os.path.join(BENCH, "traffic",
                                      cell["traffic"] + ".json")))
    assert mix["kind"] == "serve" and mix["pool_tokens"] == 393216
    assert mix["lead_in_s"] == 24
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] \
        <= mix["max_seq_len"] == 6144
    assert max(mix["decode_buckets"]) == 128
    for x in m["per_layer"]:
        if CELL in x.get("workloads", []):
            assert os.path.exists(os.path.join(BENCH, "metrics",
                                               x["name"] + ".py"))
