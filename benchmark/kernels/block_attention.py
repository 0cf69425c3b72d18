"""What the block pass's attention needs, from the shapes.

A block engine (``paddle_tpu/serving/sdar_engine.py``) hands
``paged_attention_decode`` the ``block_len x group`` queries of each KV
head as one group: one call a layer a pass. The trace calls the kernel
``paged_attention_decode_grouped`` where that group is wide enough for
the kernel's MXU body (a multiple of 16, as at the published 4 x 8) and
``paged_attention_decode`` where it is not; the work is the same, and
the breakdown's ``device_ops`` say which ran.
For the sequences of a pass with live lengths ``lens`` (the block's end),
nkv KV heads of size d and q = heads x block_len query rows a sequence:
4 q d sum(lens) operations; the bytes are the LIVE K and V, 2 nkv d
sum(lens) elements in the pool's type, and the query and output rows:
what the algorithm needs, whatever pages the kernel walks. ``ticks``
holds a sequence's length once for each position of its block.
"""
from __future__ import annotations

ITEMSIZE = {"bfloat16": 2, "float32": 4}


def kind_of(op_name):
    """Either body of the decode kernel (the wide-group one's name holds
    the other's)."""
    return "block" if "paged_attention_decode" in op_name else None


def needs(run):
    ticks = run.counters.get("ticks")
    traced = [(s, e) for name, s, e in run.spans.records if name == "traced"]
    if not ticks or not traced or "generation" not in run.config:
        return {}
    cfg = run.config
    bl = cfg["generation"]["block_length"]
    nkv, d = cfg["num_key_value_heads"], cfg["head_dim"]
    q = cfg["num_attention_heads"] * bl
    size = ITEMSIZE[cfg["serving"]["kv_dtype"]]
    lo, hi = traced[0]
    calls = []
    for end, lens, _bucket in ticks:
        if lo <= end <= hi:
            live, n = float(sum(lens[::bl])), len(lens) // bl
            calls += [(4.0 * q * d * live,
                       size * (2.0 * nkv * d * live + 2.0 * q * d * n))
                      ] * cfg["num_hidden_layers"]
    return {"block": calls}
