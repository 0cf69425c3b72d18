"""CPU rehearsals of the sparse latent serving cell, beside
``test_rehearsal_hybrid.py`` (run by hand: ``python -m pytest
benchmark/tests -q``). The ``tiny-sparse`` cell stands in a rehearsal
manifest of its own (``manifest_glm5.json``), made of data files like the
others: ``configs/glm5-tiny.json``, ``traffic/tiny-sparse.json``,
``limits/tiny-sparse.json``. The tiny cell serves in float32, so that
what the comparison tells of each planted fault stands apart from
rounding.
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
MANIFEST = os.path.join(HERE, "manifest_glm5.json")
CELL = "serve-glm52-longctx-steady"
STAND_INS = ("fp8",) + ("selection_ignored", "index_share_ignored",
                        "k_rope_dropped", "bias_dropped", "scale_dropped",
                        "shared_expert_dropped")


def drive(cell, trace=0, seed=(1 << 31) + 41, seconds=1.5, **steering):
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
    from models import glm5
    return glm5, glm5.load_config(
        os.path.join(HERE, "configs", "glm5-tiny.json"))


def _limits():
    return json.load(open(os.path.join(HERE, "limits",
                                       "tiny-sparse.json")))["limits"]


def test_cell_end_to_end():
    line, record = drive("tiny-sparse")
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"itl_p90_ms", "setup_s"}
    assert set(line["compared"]) == {"logit_gap", "selection_miss",
                                     "router_gap", "never_finished",
                                     "compiles_in_window"}
    assert record.counters["checked_tokens"] > 0
    assert record.counters["checked_selections"] > 0
    assert record.counters["checked_routes"] > 0
    assert line["compared"]["selection_miss"]["value"] == 0.0
    assert line["compared"]["router_gap"]["value"] < 1e-4


def test_cell_traced():
    line, record = drive("tiny-sparse", trace=1)
    assert line["correct"] is True, line["compared"]
    names = {m["name"] for m in json.load(open(MANIFEST))["per_layer"]}
    assert set(line["metrics"]) <= names
    for name in ("cache_bytes_per_token.serve", "decode_host_ms.serve",
                 "decode_tick_ms.serve", "pool_peak_share.serve",
                 "sched_self_ms.serve"):
        assert name in line["metrics"], name
    # five layers of a row filled to 256 numbers and two index keys of 16,
    # float32
    assert line["metrics"]["cache_bytes_per_token.serve"]["value"] \
        >= 4 * (5 * 256 + 2 * 16)
    # a share of a peak or of a roofline has nothing to read off the chip
    assert not [m for m in line["metrics"] if "mfu" in m or "roofline" in m]
    from harness import core
    for kernel, kinds in (("mla_indexer.py", {"decode", "prefill"}),
                          ("sparse_mla_decode.py", {"decode"}),
                          ("sparse_mla_prefill.py", {"prefill"}),
                          ("held_expert_ffn.py", {"held"})):
        needs = core.load_module(os.path.join(
            BENCH, "kernels", kernel)).needs(record)
        assert set(needs) <= kinds and needs, kernel
        for each in needs.values():
            assert all(f >= 0 and b >= 0 for f, b in each)
            assert any(f > 0 or b > 0 for f, b in each)


def test_control_is_not_correct():
    """The float8 reference in the program's place, through the harness's
    own comparison."""
    line, _ = drive("tiny-sparse", lower_precision=True)
    assert line["correct"] is False, line["compared"]
    gap = line["compared"]["logit_gap"]
    assert gap["value"] > 1.5 * gap["limit"]


@pytest.fixture(scope="module")
def served():
    """Prompts and served tokens drawn at random (a stand-in is held
    against the sound reference at every position of both, so any context
    will do), and a probe at a position past the selection's size."""
    fam, cfg = _tiny()
    rng = np.random.default_rng(5)
    reqs = [(rng.integers(0, cfg["vocab_size"], p),
             rng.integers(0, cfg["vocab_size"], n))
            for p, n in ((24, 12), (50, 20), (70, 25))]
    return fam, cfg, fam.whole_weights(cfg, 5), reqs


def test_sound_reference_holds_every_served_token(served):
    fam, cfg, whole, reqs = served
    for prompt, tokens in reqs:
        ids = np.concatenate([prompt, tokens])
        logits, sel, routes = fam.reference_logits(cfg, whole, ids)
        logits = np.asarray(logits)
        at = len(ids) - 3
        probe = np.stack([
            np.where(sel[src][1][at], sel[src][0][at], -1)
            for src in (fam.share_source(cfg, l)
                        for l in range(cfg["num_hidden_layers"]))])
        experts, gates = (np.stack([routes[l][i][at] for l in
                                    sorted(routes)]) for i in (0, 1))
        gaps, misses, gates_off = fam.served_checks(
            cfg, {"seed": 5}, prompt, tokens, [(at, probe, experts, gates)],
            whole=whole)
        assert len(gaps) == len(tokens) and float(gaps.min()) >= 0
        rows = np.arange(len(prompt) - 1, len(ids) - 1)
        want = logits[rows].max(-1) - logits[rows, tokens]
        assert np.abs(gaps - want).max() < 1e-5
        assert misses.tolist() == [0.0] * cfg["num_hidden_layers"]
        assert len(gates_off) == len(routes) == 4
        assert float(gates_off.max()) < 1e-6
        # the scale dropped reads 1.5 whatever else it leaves
        assert abs(fam.router_gap((experts[0], gates[0] / 2.5),
                                  (experts[0], gates[0])) - 1.5) < 1e-5


@pytest.mark.parametrize("stand_in", STAND_INS)
def test_stand_in_in_the_programs_place(served, stand_in):
    """The control and each fault planted in the reference (what
    ``glm5_controls.py --what faults`` does on the chip at the published
    size), held against the sound reference by the cell's comparison:
    each reads a ``logit_gap`` past the tiny cell's limit, a fault of the
    selection a ``selection_miss`` past it too, and a fault of the router
    a ``router_gap`` past it."""
    fam, cfg, whole, reqs = served
    kw = {"mode": stand_in} if stand_in == fam.SERVING_CONTROL \
        else {"fault": stand_in}
    assert stand_in in fam.FAULTS + (fam.SERVING_CONTROL,)
    limits = _limits()
    gaps, misses, routes = [], [], []
    for prompt, tokens in reqs:
        at = len(prompt) + len(tokens) - 2
        # a stand-in's own selections and routing are read, not these
        g, m, r = fam.served_checks(
            cfg, {"seed": 5}, prompt, tokens, [(at, np.full((5, 16), -1),
                                                np.zeros((4, 4), int),
                                                np.zeros((4, 4)))],
            whole=whole, **kw)
        gaps.append(float(g.max()))
        misses.append(float(m.mean()))
        routes.append(float(r.mean()))
    assert max(gaps) > limits["logit_gap"], (stand_in, gaps)
    if stand_in in ("selection_ignored", "index_share_ignored", "fp8"):
        assert max(misses) > limits["selection_miss"], (stand_in, misses)
    if stand_in in ("bias_dropped", "scale_dropped"):
        assert min(routes) > 10 * limits["router_gap"], (stand_in, routes)


def test_weights_by_layer_are_the_program_layout():
    fam, cfg = _tiny()
    seed = (1 << 31) + 7
    whole = fam.init_weights(cfg, seed, dtype="float32")
    assert fam.init_weights(cfg, seed) == {"seed": seed}
    from paddle_tpu.models.glm5 import glm5_layer_shapes
    pc = fam.program_config(cfg)
    for l in range(cfg["num_hidden_layers"]):
        one = fam.layer_weights(cfg, seed, l)
        assert {k: v.shape for k, v in one.items()} \
            == glm5_layer_shapes(pc, l)
        for k, v in one.items():
            assert (np.asarray(whole["layers"][l][k])
                    == np.asarray(v)).all(), (l, k)
    assert whole["layers"][1]["router"].dtype == np.float32


def test_operation_counts_at_the_published_widths():
    fam, _ = _tiny()
    cfg = fam.load_config(os.path.join(BENCH, "configs",
                                       "glm-5.2-ep16.json"))
    # the configuration file's arithmetic (the attention's parts make
    # 165.0 M a layer, not the 164.0 M of a rounder sum: 5 M more in all)
    assert abs(fam.param_count(cfg) - 3881.5e6) < 0.2e6
    assert abs(fam.param_count(cfg, [0]) - 400.9e6) < 0.2e6
    assert abs(fam.param_count(cfg, [1]) - 808.3e6) < 0.2e6
    assert abs(fam.param_count(cfg, [4]) - 817.7e6) < 0.2e6
    chunk = cfg["serving"]["prefill_chunk"]
    ctx = list(range(1, chunk + 1)) + [30000, 30001]
    assert fam._prompt_runs(ctx, chunk) == chunk
    whole = fam.serve_flops(cfg, ctx)
    produced = fam.serve_flops(cfg, ctx[chunk:], prompt_positions=0)
    prompt = fam.serve_flops(cfg, ctx[:chunk], prompt_positions=chunk)
    assert abs(whole - produced - prompt) < 1e-6 * whole
    # a produced token at 30,000: 2 x 1,303 M (attention 5 x 165 M, two
    # indexers, the dense MLP, four routers and shared experts and half an
    # expert a layer) = 2.61 G, attention over 2,048 rows in five layers
    # 1.43 G, the index score over 30,000 keys twice 0.49 G, the head
    # 0.24 G
    per = produced / 2
    assert 4.7e9 < per < 4.8e9, per


def test_manifest_additions_are_within_the_contract():
    m = json.load(open(os.path.join(os.path.dirname(BENCH),
                                    "BENCHMARK.json")))
    cell = {c["name"]: c for c in m["workloads"]}[CELL]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert sum(c["chips"] == 4 for c in m["workloads"]) == 1
    cfg = json.load(open(os.path.join(BENCH, "configs",
                                      "glm-5.2-ep16.json")))
    entry = {c["name"]: c for c in m["configs"]}[cell["config"]]
    assert cfg["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert entry["source"] == cfg["source"] and len(entry["why"]) <= 200
    mix = json.load(open(os.path.join(BENCH, "traffic",
                                      cell["traffic"] + ".json")))
    assert mix["kind"] == "serve_sparse" and mix["pool_tokens"] >= 524288
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] \
        <= mix["max_seq_len"] == 67584
    assert max(mix["decode_buckets"]) == 16
    for x in m["per_layer"]:
        if CELL in x.get("workloads", []):
            assert os.path.exists(os.path.join(BENCH, "metrics",
                                               x["name"] + ".py"))
