"""What a tick's attention over the window layers' rings needs, from the
shapes.

Every window layer of the hybrid engine
(``paddle_tpu/serving/phi4flash_engine.py``) keeps a sequence's last
``sliding_window`` K/V rows in its state slot, laid out as fixed pages,
and reads them in a decode tick through the paged decode kernel that
reads the shared pages, ``window_attention_decode`` in the trace: one
call a window layer a tick (a chunk attends its window densely in XLA and
calls nothing of this name). A sequence of live length ``len`` has
``min(len, sliding_window)`` rows in its window, the newest its own. As
``kernels/shared_kv_decode.py`` counts: nh x 6 d operations a row, and in
bytes the window's K and V once, 2 x nkv x d elements a row in the
cache's type, and the query and output rows: what the algorithm needs,
whatever part of a ring the kernel walks.
"""
from __future__ import annotations

ITEMSIZE = {"bfloat16": 2, "float32": 4}


def kind_of(op_name):
    """By the instruction's own name, as ``shared_kv_decode.kind_of``."""
    return "window" if "window_attention_decode" \
        in op_name.split(" = ", 1)[0] else None


def needs(run):
    ticks = run.counters.get("ticks")
    traced = [(s, e) for name, s, e in run.spans.records if name == "traced"]
    if not ticks or not traced or "sliding_window" not in run.config:
        return {}
    cfg = run.config
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, window = cfg["hidden_size"] // nh, cfg["sliding_window"]
    layers = run.model.layer_kinds(cfg).count("window")
    size = ITEMSIZE[cfg["serving"]["kv_dtype"]]
    lo, hi = traced[0]
    calls = []
    for end, lens, _bucket in ticks:
        if lo <= end <= hi:
            rows, n = float(sum(min(l, window) for l in lens)), len(lens)
            calls += [(nh * 6.0 * d * rows,
                       size * (2.0 * nkv * d * rows + 2.0 * nh * d * n))
                      ] * layers
    return {"window": calls}
