"""What a tick's attention over the shared pages needs, from the shapes.

The hybrid engine (``paddle_tpu/serving/phi4flash_engine.py``) keeps one
layer's K/V in the page pool, and in every decode tick the full layer and
each cross layer read it: ``1 + (cross layers)`` calls a tick of the
paged decode kernel, ``shared_kv_attention_decode`` in the trace (the
window layers' rings go through the same kernel under another name, and a
prompt's last chunk calls it for one sequence as
``shared_kv_attention_last``: neither is counted here). For the sequences
of a tick with live lengths ``lens``, nh query and nkv KV heads of size
d, a query head scoring against one key head (2 d) and weighting a pair
of value heads (2 x 2 d): nh x 6 d x sum(lens) operations a call; the
bytes are the LIVE K and V once, 2 x nkv x d x sum(lens) elements in the
pool's type, and the query (nh x d) and output (nh / 2 pairs x 2 d)
rows: what the algorithm needs, whatever rows and pages the kernel
walks.
"""
from __future__ import annotations

ITEMSIZE = {"bfloat16": 2, "float32": 4}


def kind_of(op_name):
    """By the instruction's own name (an event's name is the instruction's
    whole text, and the fusion that takes the kernel's output names the
    kernel among its operands)."""
    return "shared" if "shared_kv_attention_decode" \
        in op_name.split(" = ", 1)[0] else None


def needs(run):
    ticks = run.counters.get("ticks")
    traced = [(s, e) for name, s, e in run.spans.records if name == "traced"]
    if not ticks or not traced or "sliding_window" not in run.config:
        return {}
    cfg = run.config
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // nh
    readers = 1 + run.model.layer_kinds(cfg).count("cross")
    size = ITEMSIZE[cfg["serving"]["kv_dtype"]]
    lo, hi = traced[0]
    calls = []
    for end, lens, _bucket in ticks:
        if lo <= end <= hi:
            live, n = float(sum(lens)), len(lens)
            calls += [(nh * 6.0 * d * live,
                       size * (2.0 * nkv * d * live + 2.0 * nh * d * n))
                      ] * readers
    return {"shared": calls}
