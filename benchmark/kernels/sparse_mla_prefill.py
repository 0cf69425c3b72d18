"""What a chunk's latent attention over the selected rows needs, from the
shapes.

A prompt's chunk (``paddle_tpu/serving/glm5_engine.py:glm5_chunk_fn``)
calls ``sparse_mla_prefill`` once a layer: every query of the chunk over
the rows its layer's selection, or the one below it, picked. The kernel
reads the sequence's pages whole and masks the rows not selected, but
what the algorithm needs is what a query reads: the query at position
``t`` reads ``min(t + 1, index_topk)`` rows, and a row costs and weighs
what ``sparse_mla_decode.py`` says.
"""
from __future__ import annotations

import os

from harness import core

_DECODE = core.load_module(os.path.join(os.path.dirname(__file__),
                                        "sparse_mla_decode.py"))


def kind_of(op_name):
    return "prefill" if "sparse_mla_prefill" in op_name.split(" = ", 1)[0] \
        else None


def needs(run):
    traced = [(s, e) for name, s, e in run.spans.records if name == "traced"]
    cfg = run.config
    if not traced or "index_topk" not in cfg:
        return {}
    lo, hi = traced[0]
    k, L = cfg["index_topk"], cfg["num_hidden_layers"]
    calls = []
    for end, at, clen in run.counters.get("chunks", ()):
        if lo <= end <= hi:
            rows = float(sum(min(at + i + 1, k) for i in range(clen)))
            calls += [(_DECODE.row_flops(cfg) * rows,
                       _DECODE.row_bytes(cfg) * rows
                       + _DECODE.query_bytes(cfg) * clen)] * L
    return {"prefill": calls} if calls else {}
