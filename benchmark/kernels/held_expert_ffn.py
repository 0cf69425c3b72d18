"""What the held experts' product needs: the program's own count of the
assignments to each held expert.

The sparse latent engine routes every token over all of the router's
experts and computes only the experts this chip holds
(``paddle_tpu/models/glm5.py:held_expert_ffn``): the grouped matmul
(``grouped_ragged-dot`` in the trace) twice an expert layer, gate and up
(hidden -> 2 x width) then down (width -> hidden), over the assignments
to held experts alone. How many there are follows the routing, so the
program counts them: an ``experts.held`` span a program (``assigned``
and ``active`` a list over the expert layers: the assignments, and the
held experts that got one). For A assignments over E experts: 4 H F A
and 2 H F A operations; bytes the E experts' weights once and the rows
in and out, in bfloat16.
"""
from __future__ import annotations

from harness import program_spans as ps


def kind_of(op_name):
    return "held" if "grouped_ragged-dot" in op_name.split(" = ", 1)[0] \
        else None


def needs(run):
    cfg = run.config
    if "router_experts" not in cfg:
        return {}
    H, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    calls = []
    for s in ps.named(ps.traced(run), "experts.held"):
        for a, e in zip(s.attrs["assigned"], s.attrs["active"]):
            calls.append((4.0 * H * F * a,
                          2.0 * (e * H * 2 * F + a * (H + 2 * F))))
            calls.append((2.0 * H * F * a, 2.0 * (e * F * H + a * (F + H))))
    return {"held": calls} if calls else {}
