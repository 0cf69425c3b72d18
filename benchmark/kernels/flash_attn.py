"""What the flash-attention kernels need, from the shapes.

The train step calls three Pallas kernels per layer
(``paddle_tpu/kernels/flash_attention.py``): the forward kernel (twice
where the layer is rematerialised: once forward, once again in the
backward pass), the dq kernel and the dk/dv kernel. They carry no
``name=``: in the trace they are the ``tpu_custom_call`` operations of
the step, under the name of the jaxpr each sits in (``%closed_call.13``
and ``%rematted_computation.11``: forward; ``%checkpoint.22``: dq;
``%checkpoint.23``: dk/dv; looked at by hand, PR 26), so they are told
apart by what they return: ``(bf16, f32)`` the output and the row sums,
one ``bf16`` array dq, ``(bf16, bf16)`` dk and dv.

Per call, on one chip, with b rows (batch / dp), n heads (heads / mp),
sequence S, head size d, causal, bf16: a product of two [S, S]-by-d
operands is 2 b n S^2 d operations, halved by the causal mask. Forward:
QK^T and PV, reads q k v, writes o and the row sums. dq: QK^T, dO V^T
and dS K; reads q k v dO and the row vectors, writes dq. dk/dv: QK^T,
dO V^T, P^T dO and dS^T Q; reads q k v dO, writes dk dv. These are the
operations each kernel's own algorithm needs, not what it executes.
"""
from __future__ import annotations

from harness.trace import PALLAS

MATMULS = {"fwd": 2, "dq": 3, "dkv": 4}
TENSORS = {"fwd": 4, "dq": 5, "dkv": 6}     # [b, n, S, d] reads + writes


def kind_of(text):
    if PALLAS not in text or " = " not in text:
        return None
    returns = text.split(" = ", 1)[1].split(" custom-call(", 1)[0]
    if not returns.startswith("("):
        return "dq"
    return "fwd" if "f32[" in returns else "dkv"


def needs(run):
    job, cfg = run.traffic, run.config
    if "seq" not in job:
        return {}
    spans = [s for s in run.trace_summary["spans"] if s[2] == "train.step"]
    lo, hi = run.trace_summary["t0_s"], \
        run.trace_summary["t0_s"] + run.trace_summary["window_s"]
    steps = sum(lo <= s[0] and s[1] <= hi for s in spans)
    b = job["batch"] // job["dp"]
    n = cfg["num_heads"] // job["mp"]
    S, d = job["seq"], cfg["hidden_size"] // cfg["num_heads"]
    product = 2.0 * b * n * S * S * d / 2.0
    tensor = 2.0 * b * n * S * d
    rows = 4.0 * b * n * S
    per_layer = {"fwd": 2 if job["remat"] else 1, "dq": 1, "dkv": 1}
    return {kind: [(MATMULS[kind] * product,
                    TENSORS[kind] * tensor + 2 * rows)]
            * (steps * cfg["num_layers"] * per_layer[kind])
            for kind in MATMULS}
