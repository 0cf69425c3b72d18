"""What the indexer's score needs, from the shapes.

The sparse latent engine (``paddle_tpu/serving/glm5_engine.py``) scores a
query against every index key of its sequence in each layer that has an
indexer: ``mla_indexer_decode`` once a tick a layer (every sequence of the
bucket, one query each), ``mla_indexer_prefill`` once a chunk a layer (the
chunk's queries against the keys up to its end). A query against a key:
``index_n_heads`` dots of ``index_head_dim`` (2 operations a term), the
ReLU and the weighed sum beside them not counted. What the algorithm
needs: a query ``t`` scores the keys ``s <= t`` only; the bytes are the
live keys once a call (bfloat16), the queries, and the scores written
(float32, one a query and key), whatever pages the kernel walks.
"""
from __future__ import annotations


def kind_of(op_name):
    own = op_name.split(" = ", 1)[0]
    return "decode" if "mla_indexer_decode" in own else \
        "prefill" if "mla_indexer_prefill" in own else None


def needs(run):
    traced = [(s, e) for name, s, e in run.spans.records if name == "traced"]
    cfg = run.config
    if not traced or "index_topk" not in cfg:
        return {}
    lo, hi = traced[0]
    layers = sum(cfg["indexer_types"][l] == "full"
                 for l in cfg["served_layers"])
    nI, dI = cfg["index_n_heads"], cfg["index_head_dim"]
    per_key = nI * 2.0 * dI
    decode, prefill = [], []
    for end, lens, _bucket in run.counters.get("ticks", ()):
        if lo <= end <= hi:
            keys, n = float(sum(lens)), len(lens)
            decode += [(per_key * keys, 2.0 * dI * keys
                        + 2.0 * nI * dI * n + 4.0 * keys)] * layers
    for end, at, clen in run.counters.get("chunks", ()):
        if lo <= end <= hi:
            pairs = clen * at + clen * (clen + 1) / 2.0
            prefill += [(per_key * pairs, 2.0 * dI * (at + clen)
                         + 2.0 * nI * dI * clen + 4.0 * pairs)] * layers
    return {k: v for k, v in (("decode", decode), ("prefill", prefill))
            if v}
