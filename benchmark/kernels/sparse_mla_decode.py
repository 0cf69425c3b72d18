"""What a tick's latent attention over the selected rows needs, from the
shapes.

The sparse latent engine (``paddle_tpu/serving/glm5_engine.py``) calls
``sparse_mla_decode`` once a layer a tick. The kernel fetches each
sequence's selected latent rows itself (at most ``index_topk``, the
positions its layer's selection, or the one below it, picked): a DMA a
row from the pool in HBM, through the page table, and no other latent
row. A sequence of live length ``len`` reads ``min(len, index_topk)``
rows, each ``kv_lora_rank + qk_rope_head_dim`` = 576 numbers in bfloat16
(1,152 B: what the algorithm needs; the pool lays a row out in 1,536);
besides, its query's head rows come in and its float32 outputs go out.
The kernel scores each row with every head's absorbed query (576 terms)
and weighs its ``kv_lora_rank`` = 512, 2 operations a term: heads x 2 x
(576 + 512) a row.
"""
from __future__ import annotations


def kind_of(op_name):
    return "decode" if "sparse_mla_decode" in op_name.split(" = ", 1)[0] \
        else None


def row_flops(cfg):
    return cfg["num_attention_heads"] * 2.0 * (
        2 * cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])


def row_bytes(cfg):
    return 2.0 * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])


def query_bytes(cfg):
    """A query's head rows in and its float32 outputs out."""
    nh, kl = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    return nh * (2.0 * (kl + cfg["qk_rope_head_dim"]) + 4.0 * kl)


def needs(run):
    traced = [(s, e) for name, s, e in run.spans.records if name == "traced"]
    cfg = run.config
    if not traced or "index_topk" not in cfg:
        return {}
    lo, hi = traced[0]
    k, L = cfg["index_topk"], cfg["num_hidden_layers"]
    calls = []
    for end, lens, _bucket in run.counters.get("ticks", ()):
        if lo <= end <= hi:
            rows = float(sum(min(n, k) for n in lens))
            calls += [(row_flops(cfg) * rows,
                       row_bytes(cfg) * rows
                       + query_bytes(cfg) * len(lens))] * L
    return {"decode": calls} if calls else {}
