"""What a prefill chunk's selective scan needs, from the shapes.

``selective_scan_chunk`` (``paddle_tpu/kernels/selective_scan.py``, named
so in the trace) runs once a state-space layer in every prefill chunk.
For a chunk of ``len`` real positions, Di channels and N states: the
recurrence is 9 operations a position, channel and state (the decay's
exponent and exponential, the state's multiply-add, the input's two
products, the output's multiply and sum); the bytes are ``x``, ``dt`` and
``y`` at 4 B a position and channel, ``B`` and ``C`` at 4 B a position
and state, and the state in and out. The kernel walks the chunk's padded
256 rows whatever ``len`` is; what is counted is what the real rows need.
"""
from __future__ import annotations


def kind_of(op_name):
    """By the instruction's own name, not its operands'."""
    return "scan" if "selective_scan_chunk" \
        in op_name.split(" = ", 1)[0] else None


def needs(run):
    chunks = run.counters.get("chunks")
    traced = [(s, e) for name, s, e in run.spans.records if name == "traced"]
    if not chunks or not traced or "state_space" not in run.config:
        return {}
    s = run.model.dims(run.config)
    layers = run.model.layer_kinds(run.config).count("mamba")
    lo, hi = traced[0]
    calls = []
    for end, _at, n in chunks:
        if lo <= end <= hi:
            calls += [(9.0 * n * s["Di"] * s["N"],
                       4.0 * (3 * n * s["Di"] + 2 * n * s["N"]
                              + 2 * s["N"] * s["Di"]))] * layers
    return {"scan": calls}
