"""The driver of a serving cell whose program selects the rows its
attention reads (traffic ``"kind": "serve_sparse"``).

``drivers/serve.py``'s open loop, window and drain run unchanged (its
``serve()`` is called). Beside its comparison of logits stand two more,
from the same run of the program: the family's engine reads back, at
every ``selection_every``-th position of a sequence, the positions each
layer's attention read there and each expert layer's chosen experts and
their gates (on the timed path, one small readback a time), and the
reference's at those positions of the checked requests are held against
them: ``selection_miss`` (1 less the mean over (position, layer) of the
share of the two selections' union that both hold) and ``router_gap``
(the mean over (position, expert layer) of the family's
``router_gap``: the gates' differences summed over every routed
expert). A selection left out, or a router's bias or scale, may hide
inside a tolerance on logits; here they cannot.

Besides ``drivers/serve.py``'s, a traffic file of this kind holds
``selection_every``.
"""
from __future__ import annotations

import gc
import os

import numpy as np

from harness import core

_SERVE = core.load_module(os.path.join(os.path.dirname(__file__),
                                       "serve.py"))


def serve(run):
    """``serve.py``'s ``serve()`` with the engine's selections kept:
    ``[(prompt, served tokens, selections read)]`` of the requests the
    reference reads. The engine is found by wrapping the family's
    ``build_engine`` for the call, and its prompts by wrapping the
    engine's ``prefill_begin`` (a preempted sequence's first prompt
    stands)."""
    built, build, prompts = [], run.model.build_engine, {}

    def keep(cfg, mix, seed):
        engine = build(cfg, mix, seed)
        begin = engine.prefill_begin

        def prefill_begin(seq_id, prompt_ids):
            prompts.setdefault(seq_id, np.asarray(
                prompt_ids, np.int32).tobytes())
            return begin(seq_id, prompt_ids)

        engine.prefill_begin = prefill_begin
        built.append(engine)
        return engine

    run.model.build_engine = keep
    try:
        served = _SERVE.serve(run)
    finally:
        run.model.build_engine = build
    log = built[0].selection_log
    seq_of = {key: sid for sid, key in prompts.items()}
    out = [(prompt, tokens, log.get(seq_of.get(
        np.asarray(prompt, np.int32).tobytes()), []))
        for prompt, tokens in served]
    built[0].selection_log = {}
    return out


def run(run):
    """The cell, then the comparison. Under ``lower_precision`` (the
    control) the reference in the family's ``SERVING_CONTROL`` precision
    stands in the program's place, for the tokens and the selections."""
    served = serve(run)
    gc.collect()        # the engine, its pool and the probe around it
    weights = run.model.init_weights(run.config, run.seed)
    mode = run.model.SERVING_CONTROL if run.steer.lower_precision else None
    checks = [run.model.served_checks(run.config, weights, prompt, tokens,
                                      probes, mode=mode)
              for prompt, tokens, probes in served]
    misses = np.concatenate([m for _, m, _ in checks] + [np.zeros(0)])
    routes = np.concatenate([r for _, _, r in checks] + [np.zeros(0)])
    run.counters["checked_tokens"] = int(sum(len(g) for g, _, _ in checks))
    run.counters["checked_selections"] = int(misses.size)
    run.counters["checked_routes"] = int(routes.size)
    run.compare("logit_gap", max((float(g.max()) for g, _, _ in checks),
                                 default=float("inf")))
    run.compare("selection_miss", float(misses.mean()) if misses.size
                else float("inf"))
    run.compare("router_gap", float(routes.mean()) if routes.size
                else float("inf"))
    run.compare("never_finished", run.failed)
    run.compare("compiles_in_window", run.compiles_in_window)
