#!/usr/bin/env python
"""Static program check: lint models/train steps before they hit XLA.

Runs the :mod:`paddle_tpu.analysis` pass suite (recompile hazards, host
syncs, collective-schedule consistency, AMP cast audit, dead code, the
cost/roofline model, the liveness peak-HBM estimator, and the
buffer-donation sanitizer) over the built-in model zoo — each model is
linted TWICE: the eager train-step closure (abstract tape trace → jaxpr
passes) and the recorded ``static.Program`` DAG (deadcode + AMP node
audit). No device execution: tiny configs, abstract shapes only.

``--hbm-budget-gb`` (default 16, the chip) arms the PTMM001
OOM-before-compile gate: a model whose predicted peak HBM exceeds the
budget — or any PTBD001 use-after-donate — fails the gate even under
``--errors-only``.

Usage::

    python tools/check_program.py                  # all models
    python tools/check_program.py --model gpt      # one model
    python tools/check_program.py --json           # machine-readable
    python tools/check_program.py --errors-only    # warnings don't fail

Exit code: 0 iff every report is CLEAN (no errors, no warnings —
matching ``Report.clean``; ``--errors-only`` relaxes to errors), 1
otherwise, 2 on a harness crash. Diagnostics also land in
runlog (``analysis_diagnostic`` events) when ``PADDLE_TELEMETRY_DIR`` is
set — the observability docs' diagnostics-as-runlog-events contract.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# ---------------------------------------------------------------------------
# model-zoo targets (tiny configs — the lint is abstract, keep builds fast)
# ---------------------------------------------------------------------------

def _lint_static(build, name, world_size=None, hbm_budget_gb=None):
    """Record ``build()`` into a fresh Program (with per-node source
    sites) and run the DAG passes over it."""
    from paddle_tpu import static
    from paddle_tpu.analysis import ProgramAnalyzer
    static.enable_static()
    try:
        prog = static.Program()
        prog._capture_sites = True
        with static.program_guard(prog):
            fetches = build()
        return ProgramAnalyzer(
            world_size=world_size, hbm_budget_gb=hbm_budget_gb).analyze(
            prog, fetch_list=list(fetches), name=name)
    finally:
        static.disable_static()


def lint_gpt(world_size=None, hbm_budget_gb=None):
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu import static
    from paddle_tpu.analysis import ProgramAnalyzer
    from paddle_tpu.models.gpt import (GPTForPretraining, GPTModel,
                                       GPTPretrainingCriterion,
                                       gpt_tiny_config)
    paddle.seed(0)
    cfg = gpt_tiny_config()
    model = GPTForPretraining(GPTModel(cfg))
    crit = GPTPretrainingCriterion()
    B, S = 2, 16
    ids = jax.ShapeDtypeStruct((B, S), jnp.int32)
    reports = [ProgramAnalyzer(
        world_size=world_size, hbm_budget_gb=hbm_budget_gb).analyze(
        lambda i, l: crit(model(i), l), ids, ids, name="gpt.train_step")]

    def build():
        fids = static.data("ids", [B, S], "int64")
        labels = static.data("labels", [B, S], "int64")
        loss = crit(model(fids), labels)
        return [loss]

    reports.append(_lint_static(build, "gpt.program", world_size,
                                hbm_budget_gb))
    return reports


def lint_bert(world_size=None, hbm_budget_gb=None):
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu import static
    from paddle_tpu.analysis import ProgramAnalyzer
    from paddle_tpu.models.bert import (BertForPretraining, BertModel,
                                        bert_tiny_config)
    paddle.seed(0)
    model = BertForPretraining(BertModel(bert_tiny_config()))
    B, S = 2, 16
    ids = jax.ShapeDtypeStruct((B, S), jnp.int64)
    reports = [ProgramAnalyzer(
        world_size=world_size, hbm_budget_gb=hbm_budget_gb).analyze(
        lambda i, l: model.forward_with_mlm_loss(i, l), ids, ids,
        name="bert.train_step")]

    def build():
        fids = static.data("ids", [B, S], "int64")
        labels = static.data("labels", [B, S], "int64")
        return [model.forward_with_mlm_loss(fids, labels)]

    reports.append(_lint_static(build, "bert.program", world_size,
                                hbm_budget_gb))
    return reports


def lint_ernie_moe(world_size=None, hbm_budget_gb=None):
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu import static
    from paddle_tpu.analysis import ProgramAnalyzer
    from paddle_tpu.models import (ErnieMoeForPretraining, ErnieMoeModel,
                                   ernie_moe_tiny_config)
    paddle.seed(0)
    model = ErnieMoeForPretraining(
        ErnieMoeModel(ernie_moe_tiny_config(num_hidden_layers=2)))
    B, S = 2, 16
    ids = jax.ShapeDtypeStruct((B, S), jnp.int64)
    reports = [ProgramAnalyzer(
        world_size=world_size, hbm_budget_gb=hbm_budget_gb).analyze(
        lambda i, l: model.forward_with_mlm_loss(i, l), ids, ids,
        name="ernie_moe.train_step")]

    def build():
        fids = static.data("ids", [B, S], "int64")
        labels = static.data("labels", [B, S], "int64")
        return [model.forward_with_mlm_loss(fids, labels)]

    reports.append(_lint_static(build, "ernie_moe.program",
                                world_size, hbm_budget_gb))
    return reports


def lint_serving(world_size=None, hbm_budget_gb=None):
    """Serving decode gate: (1) the pass suite over the engine's decode
    step (collective schedule stays clean — no rank-divergent ops hide
    in the serving path), and (2) the recompile proof — replay a
    randomized admission mix through the REAL continuous-batching
    scheduler (device-free shape probe) and require every decode/prefill
    signature to fall inside the engine's AOT bucket set: a shape
    outside the set would retrace per request mix at serving time
    (PTRC002-class), and the engine would raise on it."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.analysis import ProgramAnalyzer
    from paddle_tpu.analysis.core import Diagnostic, Report
    from paddle_tpu.models.gpt import (GPTForPretraining, GPTModel,
                                       gpt_tiny_config)
    from paddle_tpu.ops._dispatch import unwrap
    from paddle_tpu.serving import ServingEngine, simulate_decode_signatures
    from paddle_tpu.serving.engine import chunk_prefill_fn, decode_step_fn
    import functools

    paddle.seed(0)
    cfg = gpt_tiny_config()
    model = GPTForPretraining(GPTModel(cfg))
    # aot=False: the lint is abstract — no bucket programs compile here
    eng = ServingEngine(model, page_size=8, decode_buckets=(1, 2, 4),
                        aot=False)
    pool = eng.pool
    bucket = eng.decode_buckets[-1]
    # lint the program the engine actually compiles: the engines wrap
    # their step fns in the auto-fusion rewrite before jit, so the lint
    # targets do too (a no-op when nothing matches or the env gate is
    # off)
    from paddle_tpu.analysis import rewrite
    _fuse = (rewrite.autofuse if rewrite.autofuse_enabled()
             else (lambda f, label=None: f))
    fn = _fuse(functools.partial(decode_step_fn,
                                 eps=cfg.layer_norm_epsilon,
                                 temperature=0.0, top_k=0,
                                 use_kernel=False),
               label="serving.decode_step")

    def decode(kp, vp, tokens, positions, table, lens):
        # analyzer hands Tensor-wrapped tracers; the decode step is pure
        # jax — unwrap at the boundary (key=None: greedy)
        a = [unwrap(t) for t in (kp, vp, tokens, positions, table, lens)]
        return fn(eng.params, *a, None)

    i32 = jnp.int32
    kp = jax.ShapeDtypeStruct(pool.k_pages.shape, pool.k_pages.dtype)
    reports = [ProgramAnalyzer(
        world_size=world_size, hbm_budget_gb=hbm_budget_gb).analyze(
        decode, kp, kp,
        jax.ShapeDtypeStruct((bucket,), i32),
        jax.ShapeDtypeStruct((bucket,), i32),
        jax.ShapeDtypeStruct((bucket, pool.max_pages_per_seq), i32),
        jax.ShapeDtypeStruct((bucket,), i32),
        name="serving.decode_step")]

    diags = []
    # the closure proof runs once per ENGINE MODE — the classic
    # bucketed engine, the chunked/prefix-cache engine (whose prefill
    # side is ONE traced-offset chunk program), the disaggregated
    # engine (per-bucket prefill programs on the prefill mesh + scatter
    # landings on the decode mesh), and the block engine (SDAR-MoE:
    # chunked prefill that yields no token, a decode step that is a pass
    # over blocks and grows the pool a block at a time). Each mode's
    # allowed set must match what the real engine would AOT-compile, and
    # every signature the real scheduler requests must fall inside it.
    from paddle_tpu.models.sdar import init_sdar_weights, sdar_moe_tiny_config
    from paddle_tpu.serving import SdarServingEngine
    from paddle_tpu.serving.sdar_engine import sdar_block_step_fn
    scfg = sdar_moe_tiny_config()
    sdar_eng = SdarServingEngine(
        init_sdar_weights(scfg, 0), scfg, page_size=8, num_pages=64,
        max_seq_len=128, decode_buckets=(1, 2, 4), prefill_chunk=16,
        aot=False)
    chunk = eng.prefill_buckets[0]
    modes = {
        "classic": (dict(), eng),
        "chunked": (dict(prefill_chunk=chunk),
                    ServingEngine(model, page_size=8,
                                  decode_buckets=(1, 2, 4),
                                  prefill_chunk=chunk, aot=False)),
        "disagg": (dict(disaggregated=True),
                   ServingEngine(model, page_size=8,
                                 decode_buckets=(1, 2, 4),
                                 disaggregated=True, aot=False)),
        "blocks": (dict(prefill_chunk=sdar_eng.prefill_chunk,
                        block_len=sdar_eng.block_len), sdar_eng),
    }

    def error(msg, op):
        diags.append(Diagnostic("PTRC002", "recompile", "error", msg,
                                op=f"serving.{op}"))

    def replay(e, **sim_kw):
        return simulate_decode_signatures(
            e.decode_buckets,
            # a chunked-only engine has no one-shot buckets
            e.prefill_buckets if e.prefill_chunk is None
            else (e.max_seq_len,),
            e.pool.page_size, e.pool.num_pages, e.max_seq_len,
            n_requests=200, seed=0, **sim_kw)

    for mode, (sim_kw, mode_eng) in modes.items():
        used_d, used_p, ok_d, ok_p = replay(mode_eng, **sim_kw)
        # the closure proof is only a proof if the probe's allowed set
        # IS the set the real engine AOT-compiles
        for ok, real, what in (
                (ok_d, mode_eng.decode_signatures(), "decode"),
                (ok_p, mode_eng.prefill_signatures(), "prefill")):
            if ok != real:
                error(f"[{mode}] shape-probe allowed {what} set "
                      f"{sorted(ok, key=str)} drifted from the engine's "
                      f"AOT {what} signatures {sorted(real, key=str)}",
                      what)
        # cancellation mix: the same replay with randomized mid-decode
        # deadline cancellations through the real scheduler's cancel()
        # path. Cancel is an EVICTION — it must introduce ZERO program
        # signatures outside the AOT set (never a recompile), and the
        # probe's allowed set must not move
        cd, cp, okd_c, okp_c = replay(mode_eng, cancel_p=0.15, **sim_kw)
        if (okd_c, okp_c) != (ok_d, ok_p):
            error(f"[{mode}+cancel] probe allowed set changed under the "
                  f"cancellation mix — the cancel path must not alter "
                  f"what the engine compiles", "cancel")
        for tag, used, ok, what in (
                (mode, used_d, ok_d, "decode"),
                (mode, used_p, ok_p, "prefill"),
                (f"{mode}+cancel", cd, ok_d, "decode"),
                (f"{mode}+cancel", cp, ok_p, "prefill")):
            escaped = sorted(used - ok, key=str)
            if escaped:
                error(f"[{tag}] serving {what} requested shape(s) "
                      f"{escaped} outside the AOT bucket set "
                      f"{sorted(ok, key=str)} — every such shape "
                      f"retraces at serving time (cancel must be an "
                      f"eviction, never a recompile); widen the bucket "
                      f"config", what)
    rep = Report("serving.decode_buckets", diags)
    rep.emit()
    reports.append(rep)

    # the chunk program itself through the pass suite (abstract): it is
    # the only NEW serving-side program shape this engine family runs
    ceng = modes["chunked"][1]
    cpool = ceng.pool
    cfn = _fuse(functools.partial(chunk_prefill_fn,
                                  eps=cfg.layer_norm_epsilon,
                                  temperature=0.0, top_k=0),
                label="serving.chunk_prefill")

    def chunk_step(kp, vp, ids, off, clen, table, rows):
        a = [unwrap(t) for t in (kp, vp, ids, off, clen, table, rows)]
        return cfn(ceng.params, *a, None)

    ckp = jax.ShapeDtypeStruct(cpool.k_pages.shape, cpool.k_pages.dtype)
    C = ceng.prefill_chunk
    reports.append(ProgramAnalyzer(
        world_size=world_size, hbm_budget_gb=hbm_budget_gb).analyze(
        chunk_step, ckp, ckp,
        jax.ShapeDtypeStruct((1, C), i32),
        jax.ShapeDtypeStruct((), i32),
        jax.ShapeDtypeStruct((), i32),
        jax.ShapeDtypeStruct((1, cpool.max_pages_per_seq), i32),
        jax.ShapeDtypeStruct((C,), i32),
        name="serving.chunk_prefill"))

    # the block engine's pass program through the full pass suite (its
    # reference paths, as above: every op modelable)
    spool = sdar_eng.pool
    sfn = _fuse(functools.partial(sdar_block_step_fn, cfg=scfg,
                                  use_kernel=False),
                label="serving.sdar_block_step")

    def block_step(kp, vp, state):
        return sfn(sdar_eng.params, unwrap(kp), unwrap(vp), unwrap(state))

    skp = jax.ShapeDtypeStruct(spool.k_pages.shape, spool.k_pages.dtype)
    reports.append(ProgramAnalyzer(
        world_size=world_size, hbm_budget_gb=hbm_budget_gb).analyze(
        block_step, skp, skp,
        jax.ShapeDtypeStruct(
            (sdar_eng.decode_buckets[-1], sdar_eng._state_width), i32),
        name="serving.sdar_block_step"))
    return reports


def lint_collectives(world_size=None, hbm_budget_gb=None):
    """Compressed-collective gate, seeded both ways:

    (1) a schedule where ranks differ ONLY in wire compression
    (rank 0 int8-compressed all_reduce/reduce_scatter + in-jit ``_q``
    prims, rank 1 uncompressed) must lint CLEAN — the PTCC passes key
    collectives on (op, group, dtype, shape) with wire dtype as
    metadata, so compression never reads as schedule divergence
    (false deadlock);

    (2) a schedule with a GENUINE divergence hidden behind a compressed
    op (rank 0 compressed all_reduce, rank 1 barrier) must still raise
    PTCC001 — compression must not mask real deadlocks. The gate FAILS
    if either direction misbehaves."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu.distributed as dist
    from paddle_tpu.analysis import ProgramAnalyzer
    from paddle_tpu.analysis.core import Diagnostic, Report

    ws = world_size or 2
    SDS = jax.ShapeDtypeStruct

    def mixed_compression(x):
        if dist.get_rank() == 0:
            dist.all_reduce(x, compress="int8")
            dist.reduce_scatter(x, None, compress="int8")
            dist.prims.c_allreduce_sum_q(x, "dp", wire="int8")
        else:
            dist.all_reduce(x)
            dist.reduce_scatter(x, None)
            dist.prims.c_allreduce_sum(x, "dp")
        return x

    reports = [ProgramAnalyzer(
        world_size=ws, hbm_budget_gb=hbm_budget_gb).analyze(
        mixed_compression, SDS((8, 4), jnp.float32),
        name="collectives.mixed_compression")]

    def seeded_divergence(x):
        if dist.get_rank() == 0:
            dist.all_reduce(x, compress="int8")
        else:
            dist.barrier()
        return x

    probe = ProgramAnalyzer(world_size=ws).analyze(
        seeded_divergence, SDS((8, 4), jnp.float32),
        name="collectives.seeded_divergence", emit=False)
    diags = []
    if not any(d.code in ("PTCC001", "PTCC002")
               for d in probe.diagnostics):
        diags.append(Diagnostic(
            "PTCC001", "collective", "error",
            "seeded compressed-vs-barrier divergence was NOT flagged — "
            "the compressed-collective lint lost the deadlock signal "
            "(wire compression must be metadata, not identity)",
            op="all_reduce"))
    rep = Report("collectives.divergence_still_caught", diags)
    rep.emit()
    reports.append(rep)
    return reports


def lint_capture(world_size=None, hbm_budget_gb=None):
    """Whole-program capture gate (dy2static ``convert_call``): every
    zoo model is captured via ``to_static`` with GENUINELY NESTED
    helpers carrying tensor-dependent control flow. Three assertions
    per model, each a Report the gate fails on:

    1. **parity** — dygraph loss == to_static loss (the captured
       program computes the same numbers, nested helpers included);
    2. **capture** — the nested helpers' code objects landed in the
       conversion cache (a helper that silently escaped capture would
       still pass parity eagerly — this catches it);
    3. **lint** — the captured StaticFunction runs the full pass suite
       clean (hostsync/recompile/collective/amp over the WHOLE
       program, transitively-converted callees attributed to their
       original source).

    Unlike the other lint targets this executes the tiny models for
    real (the AST fallback converts lazily at trace time) — still
    seconds at zoo-tiny configs on CPU."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.analysis import ProgramAnalyzer
    from paddle_tpu.analysis.core import Diagnostic, Report
    from paddle_tpu.jit import dy2static as d2s
    from paddle_tpu.models.bert import (BertForPretraining, BertModel,
                                        bert_tiny_config, _mlm_head_loss,
                                        additive_attention_mask)
    from paddle_tpu.models.ernie import (_ernie_mlm_head_loss,
                                         _guard_nonfinite)
    from paddle_tpu.models.gpt import (GPTForPretraining, GPTModel,
                                       GPTPretrainingCriterion,
                                       damp_loss_spike, gpt_tiny_config)
    from paddle_tpu.models import (ErnieMoeForPretraining, ErnieMoeModel,
                                   ernie_moe_tiny_config)

    B, S = 2, 16
    reports = []

    def gate(name, entry, helpers, vocab):
        rng = np.random.default_rng(0)
        ids = paddle.to_tensor(
            rng.integers(0, vocab, (B, S)).astype(np.int64))
        labels = paddle.to_tensor(
            rng.integers(0, vocab, (B, S)).astype(np.int64))
        diags = []
        want = float(np.asarray(entry(ids, labels).numpy()))
        sf = paddle.jit.to_static(entry)
        got = float(np.asarray(sf(ids, labels).numpy()))
        if not np.isfinite(got) or not np.allclose(got, want, rtol=1e-4,
                                                   atol=1e-5):
            diags.append(Diagnostic(
                "PTCP001", "capture", "error",
                f"dygraph vs to_static loss parity broke under "
                f"whole-program capture: eager {want!r} vs captured "
                f"{got!r}", op=name))
        converted = d2s.converted_code_objects()
        for h in helpers:
            if h.__code__ not in converted:
                diags.append(Diagnostic(
                    "PTCP002", "capture", "error",
                    f"nested helper {h.__name__!r} escaped whole-program "
                    f"capture — convert_call never converted it; the "
                    f"compiled program silently runs un-rewritten "
                    f"control flow", op=name))
        rep = Report(f"{name}.capture", diags)
        rep.emit()
        reports.append(rep)
        i64 = jax.ShapeDtypeStruct((B, S), jnp.int64)
        reports.append(ProgramAnalyzer(
            world_size=world_size, hbm_budget_gb=hbm_budget_gb).analyze(
            sf, i64, i64, name=f"{name}.captured_program"))

    paddle.seed(0)
    gcfg = gpt_tiny_config()
    gmodel = GPTForPretraining(GPTModel(gcfg))
    gmodel.eval()
    crit = GPTPretrainingCriterion()

    def gpt_entry(ids, labels):
        # threshold=0 forces the damped branch (tiny-config loss ~ln V)
        return damp_loss_spike(crit(gmodel(ids), labels), threshold=0.0)

    gate("gpt.capture_nested", gpt_entry, [damp_loss_spike],
         gcfg.vocab_size)

    paddle.seed(0)
    bmodel = BertForPretraining(BertModel(bert_tiny_config()))
    bmodel.eval()

    def bert_entry(ids, labels):
        return bmodel.forward_with_mlm_loss(ids, labels,
                                            loss_spike_damping=True)

    gate("bert.capture_nested", bert_entry,
         [BertForPretraining.forward_with_mlm_loss, _mlm_head_loss,
          additive_attention_mask, damp_loss_spike],
         bmodel.bert.config.vocab_size)

    paddle.seed(0)
    mcfg = ernie_moe_tiny_config(num_hidden_layers=2)
    mmodel = ErnieMoeForPretraining(ErnieMoeModel(mcfg))
    mmodel.eval()

    def ernie_entry(ids, labels):
        return mmodel.forward_with_mlm_loss(ids, labels,
                                            nonfinite_guard=True)

    gate("ernie_moe.capture_nested", ernie_entry,
         [ErnieMoeForPretraining.forward_with_mlm_loss,
          _ernie_mlm_head_loss, _guard_nonfinite],
         mcfg.vocab_size)
    return reports


def lint_fusion(world_size=None, hbm_budget_gb=None):
    """Auto-fusion gate, seeded both ways. A deliberately glue-heavy
    unfused MoE gate+dispatch program (sizes over the PTCS004 floor) is
    traced through the analyzer:

    - rewrite ON (default): the auto-fusion pass must land — the lint
      sees the REWRITTEN program, so PTCS004 must drop to zero and
      PTCS005 must report the fused site (unless the site is explicitly
      suppressed via PADDLE_AUTOFUSE_SUPPRESS);
    - rewrite OFF (``--no-autofuse`` / PADDLE_NO_AUTOFUSE=1): the
      pre-rewrite program must still carry >= 1 PTCS004 — the inventory
      the rewrite consumes; losing it silently would blind the pass.
    """
    import jax
    import jax.numpy as jnp
    from paddle_tpu.analysis import ProgramAnalyzer
    from paddle_tpu.analysis import rewrite
    from paddle_tpu.analysis.core import Diagnostic, Report
    from paddle_tpu.kernels.moe_dispatch import (reference_moe_combine,
                                                 reference_moe_dispatch)
    from paddle_tpu.ops._dispatch import unwrap

    S, M, E, K = 4096, 512, 16, 2
    C = int(1.2 * K * S / E)

    def moe_glue(x, gw, gb, eo):
        ei, comb, val, _, _ = reference_moe_dispatch(
            x, gw, gb, num_expert=E, capacity=C, top_k=K,
            gate_kind="renorm")
        return ei, reference_moe_combine(eo, val, comb)

    fused = rewrite.autofuse(moe_glue, label="fusion.moe_glue")

    def entry(x, gw, gb, eo):
        return fused(*(unwrap(t) for t in (x, gw, gb, eo)))

    SDS = jax.ShapeDtypeStruct
    rep = ProgramAnalyzer(
        world_size=world_size, hbm_budget_gb=hbm_budget_gb).analyze(
        entry, SDS((S, M), jnp.float32), SDS((M, E), jnp.float32),
        SDS((E,), jnp.float32), SDS((E * C, M), jnp.float32),
        name="fusion.moe_glue")
    reports = [rep]
    n004 = sum(1 for d in rep.diagnostics if d.code == "PTCS004")
    n005 = sum(1 for d in rep.diagnostics if d.code == "PTCS005")
    diags = []
    if rewrite.autofuse_enabled():
        suppressed = bool(rewrite.suppressed_sites())
        if n004 and not suppressed:
            diags.append(Diagnostic(
                "PTCS004", "cost", "error",
                f"auto-fusion is ON but the glue-heavy MoE probe still "
                f"lints {n004} PTCS004 fusion opportunit"
                f"{'y' if n004 == 1 else 'ies'} — the rewrite pass "
                f"failed to consume its own inventory (match regression "
                f"or parity reject)", op="fusion.moe_glue"))
        if not n005 and not suppressed:
            diags.append(Diagnostic(
                "PTCS005", "cost", "error",
                "auto-fusion is ON but the rewritten MoE probe carries "
                "no PTCS005 annotation — either the rewrite did not "
                "fire or the cost pass lost the fused-kernel join",
                op="fusion.moe_glue"))
    elif not n004:
        diags.append(Diagnostic(
            "PTCS004", "cost", "error",
            "auto-fusion is OFF (--no-autofuse) but the pre-rewrite "
            "glue-heavy MoE probe lints no PTCS004 — the fusion-"
            "opportunity inventory the rewrite consumes went silent",
            op="fusion.moe_glue"))
    gate = Report("fusion.autofuse_gate", diags)
    gate.emit()
    reports.append(gate)
    return reports


MODELS = {"gpt": lint_gpt, "bert": lint_bert, "ernie_moe": lint_ernie_moe,
          "serving": lint_serving, "collectives": lint_collectives,
          "capture": lint_capture, "fusion": lint_fusion}


def lint_model(name, world_size=None, hbm_budget_gb=None):
    """Lint one built-in model; returns [Report, ...] (eager + static)."""
    return MODELS[name](world_size=world_size, hbm_budget_gb=hbm_budget_gb)


# ---------------------------------------------------------------------------


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="static lint over models / train steps / programs")
    ap.add_argument("--model", default="all",
                    choices=["all"] + sorted(MODELS))
    ap.add_argument("--world-size", type=int, default=None,
                    help="simulated ranks for the collective pass "
                         "(default: env world size, min 2)")
    ap.add_argument("--hbm-budget-gb", type=float, default=16.0,
                    help="per-chip HBM budget for the PTMM001 "
                         "OOM-before-compile gate (default 16, the chip; "
                         "0 disables)")
    ap.add_argument("--json", action="store_true",
                    help="one JSON line per report")
    ap.add_argument("--errors-only", action="store_true",
                    help="exit 0 despite warnings (default: any "
                         "non-clean report fails, matching Report.clean)")
    ap.add_argument("--no-autofuse", action="store_true",
                    help="lint the PRE-rewrite programs (sets "
                         "PADDLE_NO_AUTOFUSE=1): PTCS004 fusion "
                         "opportunities stay visible instead of being "
                         "consumed by the analysis.rewrite pass")
    args = ap.parse_args(argv)
    if args.no_autofuse:
        os.environ["PADDLE_NO_AUTOFUSE"] = "1"

    names = sorted(MODELS) if args.model == "all" else [args.model]
    reports = []
    for n in names:
        reports.extend(lint_model(n, world_size=args.world_size,
                                  hbm_budget_gb=args.hbm_budget_gb or None))

    failed = False
    # with the rewrite on, the zoo's whole PTCS004 inventory must be
    # consumed (each chain either rewritten — flipping to PTCS005 — or
    # explicitly suppressed); any survivor is a gate failure even
    # though PTCS004 itself is only an info
    from paddle_tpu.analysis import rewrite as _rewrite
    if _rewrite.autofuse_enabled():
        leftovers = []
        for rep in reports:
            for d in rep.diagnostics:
                if d.code != "PTCS004" or d.severity == "error":
                    continue
                site = str((getattr(d, "extra", None) or {})
                           .get("fusion", {}).get("site", ""))
                if not _rewrite._is_suppressed(site):
                    leftovers.append((rep.target_name, site))
        if leftovers:
            failed = True
            print(f"FUSION GATE: {len(leftovers)} PTCS004 chain(s) "
                  f"survived the auto-fusion rewrite: {leftovers}",
                  flush=True)
    for rep in reports:
        # a failed trace checked nothing — always a gate failure, even
        # under --errors-only
        bad = bool(rep.errors or rep.trace_error) if args.errors_only \
            else not rep.clean
        failed = failed or bad
        if args.json:
            print(json.dumps({
                "target": rep.target_name,
                "clean": rep.clean,
                "errors": len(rep.errors),
                "warnings": len(rep.warnings),
                "infos": len(rep.infos),
                "trace_error": rep.trace_error,
                "diagnostics": [
                    {"code": d.code, "pass": d.pass_name,
                     "severity": d.severity, "op": d.op, "file": d.file,
                     "line": d.line, "message": d.message}
                    for d in rep.diagnostics],
            }), flush=True)
        else:
            print(rep, flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Exception:  # harness crash ≠ lint failure
        import traceback
        traceback.print_exc()
        sys.exit(2)
