"""What the grouped expert product needs: the rows from the shapes, the
experts that got a token from the engine's count.

``jax.lax.ragged_dot`` over the tokens sorted by expert
(``paddle_tpu/models/sdar.py:moe_ffn``; the trace calls the kernel
``ragged-dot...``, and its small ``ragged-dot-metadata`` companion is not
counted) runs twice a layer in every program, block pass and prefill
chunk alike: gate and up in one product (hidden -> 2 x width), then down
(width -> hidden). For A assignments: 4 H F and 2 H F operations each, 6
H F an assignment in all. The bytes are the weights of the experts that
got a token, once, plus the rows in and out. The assignments are the
program's real rows times ``num_experts_per_tok`` (a pass: its sequences
x the block; a chunk: its real tokens; padded rows are not work the
algorithm needs), the same in every layer. Which experts got a token
follows the routing, so their number is the program's own counter
(``passes``, ``chunk_loads``); the engine's count of assignments stands
beside it and has to be the shapes' in every layer, and the experts at
most the assignments: where one is not, the engine miscounts, and there
is nothing sound to read.
"""
from __future__ import annotations

import sys

ITEMSIZE = {"bfloat16": 2, "float32": 4}


def kind_of(op_name):
    """``op_name`` is the event's whole text: the instruction's own name
    stands before `` = `` (every instruction's text holds a
    ``metadata={...}``, so the companion is told by its name)."""
    own = op_name.split(" = ", 1)[0]
    return "expert" if "ragged-dot" in own \
        and "ragged-dot-metadata" not in own else None


def needs(run):
    traced = [(s, e) for name, s, e in run.spans.records if name == "traced"]
    cfg = run.config
    if not traced or "generation" not in cfg:
        return {}
    top_k = cfg["num_experts_per_tok"]
    bl = cfg["generation"]["block_length"]
    # (end, real rows from the shapes, experts with a token and the
    # engine's assignments, layer by layer)
    programs = [(p[0], p[1] * bl, p[-2], p[-1])
                for p in run.counters.get("passes", ())] \
        + [tuple(c) for c in run.counters.get("chunk_loads", ())]
    if not programs:
        return {}
    H, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    size = ITEMSIZE[cfg["serving"]["weight_dtype"]]
    lo, hi = traced[0]
    calls = []
    for end, tokens, active, assigned in programs:
        if lo <= end <= hi:
            rows = tokens * top_k
            if any(a != rows for a in assigned) \
                    or any(not 0 < e <= min(rows, cfg["num_experts"])
                           for e in active):
                print(f"expert_ffn: a program of {tokens} rows should "
                      f"hold {rows} assignments a layer; the engine "
                      f"counted {assigned} over {active} experts",
                      file=sys.stderr)
                return {}
            for experts in active:
                calls.append((4.0 * H * F * rows, size * (
                    experts * H * 2 * F + rows * (H + 2 * F))))
                calls.append((2.0 * H * F * rows, size * (
                    experts * F * H + rows * (F + H))))
    return {"expert": calls}
