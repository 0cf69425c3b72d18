"""The driver of a training cell (traffic ``"kind": "train"``).

The timed path is ``GPTHybridTrainStep.__call__`` (or whatever the model
family's ``build_train_step`` returns): one object, built once. Set-up
drives it through its first steps, on batches drawn from the seed whose
rows all differ, and hands the same object to the window; the reference
follows those first steps once the window has closed and the step's
state is freed. Every step ends in a loss readback.

A traffic file of this kind holds: ``batch``, ``seq`` (the global batch
and the sequence length), the mesh (``dp``, ``mp``, ``pp``, ``n_micro``),
``remat``, ``compared_steps`` (the first steps that the reference
follows), ``warm_steps`` (further steps before the window),
``reference_rows_per_pass`` and ``trace_seconds``.
"""
from __future__ import annotations

import gc

import numpy as np

from harness import peaks, trace


class Feed:
    """Batches from the seed, made on the host as the run goes: ``seq + 1``
    tokens a row, uniform over the vocabulary, every row another draw;
    the labels are the ids shifted by one."""

    def __init__(self, seed, batch, seq, vocab):
        self._rng = np.random.default_rng([int(seed), 0x7EED])
        self._shape, self._vocab = (batch, seq + 1), vocab

    def next(self):
        tok = self._rng.integers(0, self._vocab, self._shape, dtype=np.int32)
        return tok[:, :-1], tok[:, 1:]


def worst_gap(got, want, skip=()):
    """The widest gap, over the leaves, between the program's norm and
    the reference's, measured against the reference's norm of that leaf
    or of the median leaf, whichever is larger."""
    median = float(np.median(np.concatenate(
        [np.ravel(v) for v in want.values()])))
    worst, where = 0.0, None
    for key, ref in want.items():
        gap = np.abs(np.asarray(got[key]) - ref) / np.maximum(ref, median)
        for layer in np.ravel(np.argsort(-gap)):
            if (key, int(layer)) in skip:
                continue
            if gap[layer] > worst:
                worst, where = float(gap[layer]), (key, int(layer))
            break
    return worst, where


def still_leaves(ref_grad):
    """Leaves whose gradient is nought to rounding in the reference (a
    key's bias under softmax): under a thousandth of the median leaf's.
    AdamW moves them by round-off alone, so they are left out of the
    change. The rule is on the reference's gradient, not on a name."""
    median = float(np.median(np.concatenate(
        [np.ravel(v) for v in ref_grad.values()])))
    return {(key, int(i)) for key, v in ref_grad.items()
            for i in np.flatnonzero(np.ravel(v) < 1e-3 * median)}


def run(run):
    import jax
    cfg, job, model, spans = run.config, run.traffic, run.model, run.spans
    batch, seq = job["batch"], job["seq"]
    step = model.build_train_step(cfg, job, run.seed,
                                  lower_precision=run.steer.lower_precision)
    if run.steer.break_program:
        step = run.steer.break_program(step) or step
    run.mark("build_step")
    feed = Feed(run.seed, batch, seq, cfg["vocab_size"])
    beta1 = cfg["training"]["beta1"]

    def one_step():
        with spans.span("train.feed"):
            ids, labels = feed.next()
        with spans.span("train.step"):
            loss = step(ids, labels)
        with spans.span("readback"):
            return (ids, labels), float(loss.numpy())

    # ---- set-up: the first steps, which the reference will follow ------
    early, losses, grad = [], [], None
    for i in range(job["compared_steps"]):
        fed, loss = one_step()
        early.append(fed)
        losses.append(loss)
        if i == 0:
            # m after one step is (1 - beta1) times the first gradient as
            # the optimizer got it (clipped)
            run.mark("first_step")
            grad = {k: np.asarray(v) for k, v in model.leaf_norms(
                step.opt_state["m"], 1.0 / (1.0 - beta1)).items()}
    run.mark("compared_steps")
    start = model.init_weights(
        cfg, run.seed, shardings=jax.tree.map(lambda a: a.sharding,
                                              step.params))
    change = {k: np.asarray(v)
              for k, v in model.change_norms(step.params, start).items()}
    del start
    for _ in range(job["warm_steps"]):
        one_step()
    run.mark("change_and_warm_steps")

    # ---- the window ----------------------------------------------------
    recording = trace.Recording(run, job["trace_seconds"])
    t_open = now = run.open_window()
    steps = 0
    while now - t_open < run.seconds:
        if recording.due(now - t_open):
            recording.start()
        one_step()
        steps += 1
        now = spans.records[-1][2]
    run.close_window(now)
    recording.stop()
    recording.read()
    run.attempted, run.failed = steps, 0
    run.counters.update(steps=steps, tokens=steps * batch * seq,
                        tokens_per_step=batch * seq,
                        flops_per_token=model.train_flops_per_token(cfg, seq))

    # ---- peak read, state freed, then the reference --------------------
    run.memory_peak_bytes = peaks.memory_peak_bytes(run.chips)
    step.params = step.opt_state = None
    del step
    gc.collect()
    ref = model.reference_train(cfg, run.seed, early,
                                job["reference_rows_per_pass"],
                                jax.devices()[:run.chips])
    run.compare("loss_gap", max(abs(got - want) / abs(want)
                                for got, want in zip(losses, ref["loss"])))
    run.compare("grad_gap", worst_gap(grad, ref["grad"])[0])
    run.compare("change_gap", worst_gap(change, ref["change"],
                                        skip=still_leaves(ref["grad"]))[0])
    run.compare("compiles_in_window", run.compiles_in_window)
