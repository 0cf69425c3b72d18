"""What the paged decode kernel needs, from the shapes.

``paged_attention_decode`` (``paddle_tpu/kernels/paged_attention.py``,
named so in the trace) runs once per layer in every decode tick. For the
sequences of a tick with live lengths ``lens``, n heads of size d: one
query row a sequence against its live keys and values: 4 n d sum(lens)
operations; the bytes are the LIVE K and V, 2 n d sum(lens) elements in
the pool's type, and the query and output rows: what the algorithm
needs, whatever pages the kernel walks.
"""
from __future__ import annotations

ITEMSIZE = {"bfloat16": 2, "float32": 4}


def kind_of(op_name):
    return "decode" if "paged_attention_decode" in op_name else None


def needs(run):
    ticks = run.counters.get("ticks")
    if not ticks:
        return {}
    cfg = run.config
    n, d = cfg["num_heads"], cfg["hidden_size"] // cfg["num_heads"]
    size = ITEMSIZE[cfg["serving"]["kv_dtype"]]
    traced = [(s, e) for name, s, e in run.spans.records if name == "traced"]
    if not traced:
        return {}
    lo, hi = traced[0]
    calls = []
    for end, lens, _bucket in ticks:
        if lo <= end <= hi:
            live = float(sum(lens))
            calls += [(4.0 * n * d * live,
                       size * (2.0 * n * d * live + 2.0 * n * d * len(lens)))
                      ] * cfg["num_layers"]
    return {"decode": calls}
