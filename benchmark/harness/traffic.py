"""The one generator of requests; a traffic mix is a file of parameters.

A mix (``traffic/<name>.json``, ``"kind": "serve"``) gives Poisson
arrivals at a fixed rate and a log-normal distribution each of prompt
lengths and of output lengths (``median`` and ``sigma``, clipped to
``min``..``max``).

Every seed does the same work in another order. The file's ``shape_seed``
draws, once, the set of gaps and the set of (prompt, output) lengths, for
the lead-in and for the window apart (each block's gaps scaled to fill
it exactly, so the window always holds ``rate x seconds`` requests and
the same tokens); ``--seed`` draws the order of the gaps, the order of
the requests, and the token ids (elsewhere, the weights). A whole shape
drawn from the seed was tried first (PR 26): the window then held 81 to
96 requests and 139 to 162 tokens a second of work, which is the seed
changing the work, not the program.
"""
from __future__ import annotations

import numpy as np


def _lengths(rng, spec, n):
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    x = np.exp(np.log(spec["median"])
               + spec["sigma"] * rng.standard_normal(n))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def _gaps(rng, spec, n, span_s):
    if spec["process"] != "poisson":
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    g = rng.exponential(1.0, n)
    return g * (span_s / g.sum())


def _block(shape, mix, span_s):
    """The set of one block: its gaps, and its requests' lengths."""
    n = max(1, int(round(mix["arrivals"]["rate_per_s"] * span_s)))
    return (_gaps(shape, mix["arrivals"], n, span_s),
            _lengths(shape, mix["prompt_tokens"], n),
            _lengths(shape, mix["output_tokens"], n))


def _ordered(order, block):
    """One block as this seed plays it: due times, prompts, outputs."""
    gaps, prompts, outputs = block
    who = order.permutation(len(gaps))
    gaps = order.permutation(gaps)
    # a request is due where its gap begins: the block's first at its
    # start, and all of them inside it
    return (np.cumsum(gaps) - gaps, prompts[who], outputs[who])


def requests(mix, seed, lead_s, window_s, total_s, vocab):
    """``[(due_s, prompt ids, output tokens)]`` sorted by due time: the
    lead-in's block, the window's block, and the window's block again (in
    an order of its own) for as long as the drain may last (``total_s``
    seconds in all)."""
    shape = np.random.default_rng(mix.get("shape_seed", 0))
    order = np.random.default_rng([int(seed), 0x0DE])
    blocks = [(0.0, _ordered(order, _block(shape, mix, lead_s)))] \
        if lead_s > 0 else []
    window = _block(shape, mix, window_s)
    t0 = lead_s
    while t0 < total_s:
        blocks.append((t0, _ordered(order, window)))
        t0 += window_s
    ids = np.random.default_rng([int(seed), 0x70C])
    out = []
    for t0, (due, prompts, outputs) in blocks:
        for k in range(len(due)):
            prompt = ids.integers(0, vocab, int(prompts[k]), dtype=np.int32)
            out.append((t0 + float(due[k]), prompt, int(outputs[k])))
    return [r for r in out if r[0] < total_s]
