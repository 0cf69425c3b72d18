#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py [--seed N]       one TPU chip: train, then serve
    python chip_smoke.py --chips 4        four chips: sharded training only

Drives the main path once through the entry points a user calls, at the
full width of GPT-345M (hidden 1024, 24 layers, S1024; weights random,
from ``--seed``):

- *train*: ``GPTHybridTrainStep`` on a dp1·mp1·pp1 mesh, bf16 compute,
  ``remat="dots"``, B12 x S1024, one warm-up and five steps on one batch;
- *serve*: ``ServingEngine`` (defaults: Pallas paged-decode kernel,
  auto-fusion on) under ``ContinuousBatchingScheduler``, eight ragged
  prompts, 64 new tokens each; then the same prompts, the model cast to
  bf16, through a prefix-cache + chunked-prefill engine;
- ``--chips 4``: the same train step, three steps on one device and three
  from the same seed and batch on dp2·mp2, and no other phase.

Each phase checks its own results and raises on a failed check; nothing
turns a failed phase into a printed row. Every line of output is one JSON
object. The last line is ``{"ok": true, "device": {...}}`` with the device
as JAX reports it, and is printed only if every phase passed on a TPU:
any other platform, a raised phase, or a directory that holds this file
and nothing else of the repository, ends in a non-zero exit and no such
line. Speeds printed on the way are observations, not measurements.

One process uses the chip at a time: this parent never imports JAX (or
``paddle_tpu``, which does) and runs the phases as children, one after
the other, taking the device line from their reports. The children share
the persistent compilation cache (``paddle_tpu/utils/compile_cache.py``).

There is no option that makes it smaller: the CPU rehearsal is
``tests/test_chip_smoke.py``, which calls the phase functions below with
``gpt_tiny_config``.
"""
import argparse
import collections
import json
import os
import subprocess
import sys
import time

# bench.py's ragged serving mix: every prompt a different unaligned length
PROMPT_LENS = (937, 512, 701, 233, 864, 129, 395, 620)
# greedy tokens engine vs GPTGenerator must agree at least this far on
# each compared prompt (see serve_phase); the chip showed 64, 34 and 64
MIN_AGREE = 16
# Five steps from a random init leave no room for a warm-up schedule, and
# without one bench.py's steady-state 1e-4 overshoots at 345M: AdamW's
# first steps move every weight by ~lr whatever the gradient's size (first
# chip run of PR 22: 10.80, 10.90, 10.65, 10.31, 11.21). A tenth of it is
# where a warm-up would be after a few hundred steps.
LR = 1e-5


def report(phase, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


def check(ok, what):
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def device_gate(need=1):
    """The device as JAX reports it — or a refusal: this script proves
    the program on the chip, and a CPU run proves nothing about it."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found platform {devs[0].platform!r}, not a "
            f"TPU — refusing to run (no fallback)")
    if len(devs) < need:
        raise SystemExit(
            f"chip_smoke: {need} chips needed, JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _on_tpu():
    import jax
    return jax.devices()[0].platform == "tpu"


def _build_model(cfg, seed):
    """Eager f32 weights from ``seed``, built on the host backend where
    there is one (as bench.py does): only the step's or the engine's own
    copies then live in HBM."""
    import contextlib
    import jax
    import paddle_tpu
    from paddle_tpu.models.gpt import GPTForPretraining, GPTModel
    try:
        host = jax.devices("cpu")[0]
    except RuntimeError:
        host = None
    paddle_tpu.seed(seed)
    with jax.default_device(host) if host is not None \
            else contextlib.nullcontext():
        return GPTForPretraining(GPTModel(cfg))


def _batch(cfg, batch, seq, seed):
    import numpy as np
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, cfg.vocab_size, size=(batch, seq)).astype(np.int32)
    return ids, np.roll(ids, -1, axis=1).astype(np.int32)


def _train_steps(cfg, hcg_kw, batch, seq, seed, steps, warmup):
    """Build the hybrid step on the given mesh degrees, run ``warmup`` +
    ``steps`` steps on one fixed batch, every loss read back (a true
    barrier). Returns (step object, losses, first-call s, step seconds)."""
    import jax
    from paddle_tpu.distributed.mesh import HybridCommunicateGroup
    from paddle_tpu.models.gpt import GPTHybridTrainStep

    # a new group replaces the global mesh and group of the one before it
    hcg = HybridCommunicateGroup(**hcg_kw)
    step = GPTHybridTrainStep(_build_model(cfg, seed), cfg, hcg, n_micro=1,
                              lr=LR, remat="dots",
                              compute_dtype="bfloat16")
    ids, labels = _batch(cfg, batch, seq, seed)
    if _on_tpu():
        # the gate is seen to choose the flash kernel, not assumed to
        check("tpu_custom_call" in step.lower_step(batch, seq).as_text(),
              "the lowered train step holds no Pallas (flash) kernel")
    losses, times = [], []
    for _ in range(warmup + steps):
        t0 = time.perf_counter()
        losses.append(float(step(ids, labels).numpy()))
        times.append(time.perf_counter() - t0)
    jax.block_until_ready(step.params)
    return step, losses[warmup:], sum(times[:warmup]), times[warmup:]


def _peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def train_phase(cfg, batch, seq, seed, steps=5):
    import numpy as np
    _, losses, first_s, times = _train_steps(
        cfg, dict(dp_degree=1, mp_degree=1, pp_degree=1), batch, seq, seed,
        steps, warmup=1)
    check(all(np.isfinite(losses)), f"non-finite loss in {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall on a fixed batch: {losses}")
    step_ms = 1e3 * float(np.median(times))
    report("train", note="smoke, not a measurement",
           hidden=cfg.hidden_size, layers=cfg.num_layers,
           heads=cfg.num_heads, batch=batch, seq=seq, losses=losses,
           flash_kernel_in_step=_on_tpu(),
           warmup_incl_compile_s=round(first_s, 2),
           step_ms=round(step_ms, 2),
           tokens_per_s=round(batch * seq / (step_ms / 1e3), 1),
           peak_bytes_in_use=_peak_bytes())
    return losses


def _prompts(cfg, prompt_lens, seed):
    import numpy as np
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
            for n in prompt_lens]


def _serve(engine, prompts, max_new):
    """Eight ragged prompts through the scheduler; tokens by prompt."""
    from paddle_tpu.serving import ContinuousBatchingScheduler
    sched = ContinuousBatchingScheduler(engine)
    t0 = time.perf_counter()
    rids = [sched.submit(p, max_new_tokens=max_new).rid for p in prompts]
    finished = {r.rid: r for r in sched.run()}
    wall = time.perf_counter() - t0
    check(sorted(finished) == sorted(rids)
          and all(r.state == "finished" for r in finished.values()),
          f"{len(finished)} of {len(rids)} requests finished: "
          f"{[r.state for r in finished.values()]}")
    tokens = [list(map(int, finished[rid].tokens)) for rid in rids]
    check(all(len(t) == max_new for t in tokens),
          f"token counts {[len(t) for t in tokens]}, want {max_new} each")
    import numpy as np
    return tokens, wall, 1e3 * float(np.median(sched.step_times))


def _engine(model, cfg, **kw):
    """A ``ServingEngine`` whose weights are seen to live on the device
    its programs run on: the model was built on the host backend, and
    weights left there would cross to the chip again on every call."""
    import jax
    from paddle_tpu.serving import ServingEngine
    engine = ServingEngine(model, cfg, **kw)
    where = set().union(*(leaf.devices()
                          for leaf in jax.tree.leaves(engine.params)))
    check(where == {jax.devices()[0]},
          f"engine weights live on {where}, not on {jax.devices()[0]}")
    return engine


def _tolerance(want, live, dtype):
    """Outputs are convex mixes of V rows, rounded once to the pool's
    dtype: bf16 pools are held to two bf16 ulps (2^-8 each) at the
    largest output, f32 pools to f32 rounding of a 1k-term sum."""
    import jax.numpy as jnp
    import numpy as np
    if dtype != jnp.bfloat16:
        return 1e-4
    return 2 ** -7 * max(1.0, float(np.abs(np.asarray(want, np.float32))
                                    [live].max()))


def _kernel_vs_reference(engine, seed):
    """The Pallas decode kernel against the XLA reference on the engine's
    REAL pool (whatever the requests left in it): the whole pool read at
    its last layer, as the engine's programs call it, and that layer's
    pages alone (a rank-4 pool), ragged lengths with an idle slot.
    Returns (max abs error, tolerance)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.kernels.paged_attention import (
        paged_attention_decode, paged_attention_reference)
    pool = engine.pool
    last = pool.k_pages.shape[0] - 1
    kp, vp = pool.k_pages[last], pool.v_pages[last]
    check(float(jnp.abs(kp.astype(jnp.float32)).max()) > 0,
          "the pool holds no keys after serving")
    rng = np.random.default_rng(seed)
    B, pps = 8, pool.max_pages_per_seq
    cap = pps * pool.page_size
    q = jnp.asarray(rng.standard_normal(
        (B, engine.cfg.num_heads, engine.cfg.head_dim)), kp.dtype)
    table = jnp.asarray(rng.integers(1, pool.num_pages, (B, pps)),
                        jnp.int32)
    lens = rng.integers(1, cap + 1, (B,))
    lens[0], lens[1], lens[2] = cap, 0, 1       # full, idle, one token
    lens = jnp.asarray(lens, jnp.int32)
    got = paged_attention_decode(q, pool.k_pages, pool.v_pages, table,
                                 lens, layer=jnp.int32(last))
    one = paged_attention_decode(q, kp, vp, table, lens)
    # the reference's f32 einsums would otherwise take the chip's default
    # single bf16 pass and be the less exact of the two
    with jax.default_matmul_precision("highest"):
        want = paged_attention_reference(q, kp, vp, table, lens)
    live = np.asarray(lens) > 0
    check(bool((np.asarray(got, np.float32)[live]
                == np.asarray(one, np.float32)[live]).all()),
          "the decode kernel reads another layer of the whole pool than "
          "of that layer's pages alone")
    err = float(np.abs(np.asarray(got, np.float32)
                       - np.asarray(want, np.float32))[live].max())
    check(np.isfinite(np.asarray(got, np.float32)).all(),
          "non-finite row out of the decode kernel")
    tol = _tolerance(want, live, kp.dtype)
    check(err <= tol, f"decode kernel vs reference: {err} > {tol}")
    return err, tol


def _rewrite_vs_dense_gather(engine, seed):
    """The ``ragged_prefill`` rewrite on a program that still holds the
    dense page gather (the engine's own chunk program calls the ragged
    kernel itself): one chunk over one layer's pages of the engine's
    REAL pool, rewritten, against the same program left as it was.
    Returns (max abs error, tolerance)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.analysis import rewrite
    from paddle_tpu.kernels.paged_attention import paged_prefill_attention
    pool = engine.pool
    kp, vp = pool.k_pages[0], pool.v_pages[0]
    rng = np.random.default_rng(seed)
    C, pps = engine.prefill_chunk, pool.max_pages_per_seq
    q = jnp.asarray(rng.standard_normal(
        (1, C, engine.cfg.num_heads, engine.cfg.head_dim)), kp.dtype)
    table = jnp.asarray(rng.integers(1, pool.num_pages, (1, pps)),
                        jnp.int32)
    off = jnp.int32(pps * pool.page_size - C)   # the chunk ends the table
    got = jax.jit(rewrite.autofuse(
        paged_prefill_attention, label="chip_smoke.dense_gather"))(
        q, kp, vp, table, off)
    want = jax.jit(paged_prefill_attention)(q, kp, vp, table, off)
    err = float(np.abs(np.asarray(got, np.float32)
                       - np.asarray(want, np.float32)).max())
    tol = _tolerance(want, slice(None), kp.dtype)
    check(err <= tol, f"ragged_prefill rewrite vs dense gather: "
                      f"{err} > {tol}")
    return err, tol


def serve_phase(cfg, seed, prompt_lens=PROMPT_LENS, max_new=64,
                page_size=64, decode_buckets=(1, 2, 4, 8),
                prefill_buckets=(256, 512, 1024), chunk=256,
                min_agree=MIN_AGREE):
    import numpy as np
    from paddle_tpu.analysis import rewrite
    from paddle_tpu.models.gpt import GPTGenerator

    model = _build_model(cfg, seed)
    prompts = _prompts(cfg, prompt_lens, seed)
    engine_kw = dict(page_size=page_size, decode_buckets=decode_buckets,
                     prefill_buckets=prefill_buckets, temperature=0.0)

    # ---- classic engine: defaults otherwise (use_kernel, autofuse on),
    # the model as built — f32 weights, f32 pool, as bench.py serves it
    engine = _engine(model, cfg, **engine_kw)
    check(engine.use_kernel and engine.autofuse,
          "engine defaults changed: use_kernel/autofuse are off")
    tokens, wall, tick_ms = _serve(engine, prompts, max_new)
    if _on_tpu():
        check("tpu_custom_call" in
              engine._decode_exe[max(decode_buckets)].as_text(),
              "the decode executable holds no Pallas kernel")
    err, tol = _kernel_vs_reference(engine, seed)
    check(engine.pool.pages_in_use == 0,
          f"pool not drained: {engine.pool.stats()}")

    # greedy tokens against GPTGenerator on the three shortest prompts.
    # Rule: identical up to the first divergence, and on each of the
    # three no divergence before token MIN_AGREE. Token 0 comes from
    # prefill alone; every later one from the paged decode kernel, so a
    # wrong page, mask or offset diverges at token 1 on every prompt.
    # Past that the two programs differ in arithmetic only (the kernel's
    # f32 scores vs XLA's single-bf16-pass einsum over a dense cache),
    # which on the chip can flip a near-tie between two logits — with
    # random weights the top two of 50k logits are often close — and
    # after one flip the sequences are different sequences, so nothing
    # past it is compared.
    gen = GPTGenerator(model, temperature=0.0)
    agree = {}
    for i in np.argsort(prompt_lens)[:3]:
        ref = gen(prompts[i][None],
                  max_new_tokens=max_new).numpy()[0, -max_new:]
        agree[int(prompt_lens[i])] = next(
            (n for n, (a, b) in enumerate(zip(tokens[i], ref))
             if a != int(b)), max_new)
    need = min(min_agree, max_new)
    check(all(n >= need for n in agree.values()),
          f"engine and GPTGenerator diverge before token {need}: "
          f"agreeing tokens by prompt length {agree}")
    report("serve", note="smoke, not a measurement",
           hidden=cfg.hidden_size, layers=cfg.num_layers,
           heads=cfg.num_heads, requests=len(tokens),
           new_tokens_each=max_new, prompt_lens=list(prompt_lens),
           decode_kernel_in_program=_on_tpu(),
           kernel_vs_reference_max_abs_err=err, tolerance=tol,
           pool_dtype=str(engine.pool.k_pages.dtype),
           generator_rule=f"identical up to the first divergence; none "
                          f"before token {need} on any of the three "
                          f"shortest prompts",
           generator_tokens_agreeing_by_prompt_len=agree,
           pool_pages_in_use=engine.pool.pages_in_use,
           engine_compile_s=round(engine.compile_s, 2),
           wall_s=round(wall, 2), decode_tick_ms_median=round(tick_ms, 2),
           new_tokens_per_s=round(len(tokens) * max_new / wall, 1),
           peak_bytes_in_use=_peak_bytes())

    # ---- prefix cache + chunked prefill, the model cast to bf16 as a
    # 16 GB deployment would hold it: the chunk program (the ragged
    # kernel on the whole pool) and the ragged_prefill rewrite (on a
    # dense gather) compile and run, and the two serving kernels run on
    # a bf16 pool
    del engine, gen
    rewrite.reset_records()
    engine = _engine(model.bfloat16(), cfg, prefix_cache=True,
                     prefill_chunk=chunk, **engine_kw)
    tokens2, wall, tick_ms = _serve(engine, prompts, max_new)
    err, tol = _kernel_vs_reference(engine, seed)
    rewrite_err, rewrite_tol = _rewrite_vs_dense_gather(engine, seed)
    records = rewrite.match_records()
    statuses = collections.Counter(
        f"{r.get('rule') or r.get('kind')}:{r['status']}" for r in records)
    bad = [r for r in records if r["status"] in ("error", "parity_failed")]
    check(not bad, f"auto-fusion records: {bad}")
    check(any(r["status"] == "fired" and r["rule"] == "ragged_prefill"
              for r in records),
          f"the ragged_prefill rewrite did not fire: {dict(statuses)}")
    same = sum(a == b for a, b in zip(tokens, tokens2))
    report("serve_chunked", note="smoke, not a measurement",
           requests=len(tokens2), new_tokens_each=max_new,
           prefill_chunk=chunk, rewrite_statuses=dict(statuses),
           pool_dtype=str(engine.pool.k_pages.dtype),
           kernel_vs_reference_max_abs_err=err, tolerance=tol,
           rewrite_vs_dense_gather_max_abs_err=rewrite_err,
           rewrite_tolerance=rewrite_tol,
           program_memory=engine.program_memory(),
           prompts_token_identical_to_f32_classic=same,
           engine_compile_s=round(engine.compile_s, 2),
           wall_s=round(wall, 2), decode_tick_ms_median=round(tick_ms, 2),
           new_tokens_per_s=round(len(tokens2) * max_new / wall, 1),
           prefix_cache=engine.prefix_cache.stats())
    return tokens, tokens2


def sharded_phase(cfg, batch, seq, seed, steps=3):
    """Hybrid-parallel training on four devices against one device: same
    seed, same batch, losses agree; every device holds shards of the
    parameters and none holds the whole model."""
    import jax
    import numpy as np

    def run(hcg_kw):
        step, losses, first_s, times = _train_steps(
            cfg, hcg_kw, batch, seq, seed, steps, warmup=0)
        per_dev = collections.Counter()
        for leaf in jax.tree.leaves(step.params):
            for sh in leaf.addressable_shards:
                per_dev[sh.device.id] += sh.data.nbytes
        total = sum(leaf.nbytes for leaf in jax.tree.leaves(step.params))
        return losses, first_s, times, dict(per_dev), total

    one, first1, times1, _, total = run(
        dict(dp_degree=1, mp_degree=1, pp_degree=1))
    # the one-device step, its state and its programs go before the next
    jax.clear_caches()
    four, first4, times4, per_dev, _ = run(
        dict(dp_degree=2, mp_degree=2, pp_degree=1))
    check(all(np.isfinite(one + four)), f"non-finite loss: {one} {four}")
    # bf16 compute, f32 masters: the two runs do the same arithmetic in
    # another order (mp splits every matmul's contraction or output, dp
    # the batch mean); step 0 differs by summation order alone, later
    # steps carry that through AdamW. 2e-2 relative is ~5 bf16 ulps.
    tol = 2e-2
    rel = [abs(a - b) / abs(a) for a, b in zip(one, four)]
    check(max(rel) <= tol, f"losses disagree: {one} vs {four}")
    devices = sorted(d.id for d in jax.devices()[:4])
    check(sorted(per_dev) == devices,
          f"parameter shards on devices {sorted(per_dev)}, want {devices}")
    check(max(per_dev.values()) < total,
          f"a device holds the whole model: {per_dev} of {total}")
    report("sharded_train", note="smoke, not a measurement",
           hidden=cfg.hidden_size, layers=cfg.num_layers, batch=batch,
           seq=seq, mesh="dp2 x mp2", losses_one_device=one,
           losses_four_devices=four, max_rel_diff=max(rel), tolerance=tol,
           param_bytes_total=total, param_bytes_per_device=per_dev,
           first_step_incl_compile_s=[round(first1 + times1[0], 2),
                                      round(first4 + times4[0], 2)],
           step_ms=[round(1e3 * float(np.median(times1[1:])), 2),
                    round(1e3 * float(np.median(times4[1:])), 2)])
    return one, four


def _child(phase, seed):
    """One phase at full size, in this process, on the chip."""
    need = 4 if phase == "sharded" else 1
    device = device_gate(need)
    from paddle_tpu.models.gpt import gpt_345m_config
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    report(phase + "_start", device=device,
           compile_cache_dir=enable_compile_cache())
    # num_heads=8 (d_head 128): same parameters and FLOPs as the 16-head
    # shape, the one the chip history (BENCH_r03) ran
    cfg = gpt_345m_config(max_position_embeddings=1024, num_heads=8)
    if phase == "train":
        train_phase(cfg, batch=12, seq=1024, seed=seed)
    elif phase == "serve":
        serve_phase(cfg, seed)
    else:
        sharded_phase(cfg, batch=12, seq=1024, seed=seed)
    print(json.dumps({"phase": phase, "ok": True, "device": device}),
          flush=True)


def _run_child(phase, seed):
    """Run one phase as a child that owns the chip; pass its lines
    through; return the device it reported, or exit with its failure."""
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase,
           "--seed", str(seed)]
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True)
    last = ""
    try:
        for line in proc.stdout:
            line = line.rstrip("\n")
            if line:
                print(line, flush=True)
                last = line
        rc = proc.wait()
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    if rc != 0:
        raise SystemExit(f"chip_smoke: phase {phase} failed (exit {rc})")
    done = json.loads(last)
    if not (done.get("ok") is True and done.get("phase") == phase):
        raise SystemExit(f"chip_smoke: phase {phase} reported no result")
    return done["device"]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: the sharded-training comparison, and only it")
    ap.add_argument("--phase", choices=("train", "serve", "sharded"),
                    help="run one phase in this process (what the parent "
                         "starts as a child)")
    args = ap.parse_args(argv)
    if args.phase:
        _child(args.phase, args.seed)
        return 0
    phases = ("sharded",) if args.chips == 4 else ("train", "serve")
    devices = [_run_child(phase, args.seed) for phase in phases]
    if any(d != devices[0] for d in devices):
        raise SystemExit(f"chip_smoke: phases disagree on the device: "
                         f"{devices}")
    print(json.dumps({"ok": True, "device": devices[0]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
