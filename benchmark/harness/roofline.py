"""A kernel's share of its roofline, from the device trace.

A file under ``kernels/`` says which operations of the trace are the
kernel's calls (``kind_of(op_name)``: a kind, or ``None``) and what each
kind of call needs (``needs(run) -> {kind: [(flops, bytes), ...]}``, one
entry per call that the traced window should hold on one chip, from the
shapes). The least time of a call is the larger of operations over the
peak rate and bytes over the memory bandwidth; the share is the sum of
those over the kernel's own time in the trace. Where the trace holds
another number of calls than the shapes foretell (by more than a tenth),
the reader has nothing sound to read and returns nothing: it never
returns 0, and nothing is clipped to 100.
"""
from __future__ import annotations

import collections


def share(run, kernel):
    summary, peaks = run.trace_summary, run.peaks
    if summary is None or peaks is None:
        return None
    needs = kernel.needs(run)
    if not needs:
        return None
    spent = collections.defaultdict(float)
    calls = collections.defaultdict(float)
    for device in summary["devices"].values():
        for name, (seconds, n) in device["ops"].items():
            kind = kernel.kind_of(name)
            if kind is not None:
                spent[kind] += seconds / len(summary["devices"])
                calls[kind] += n / len(summary["devices"])
    least = total = 0.0
    for kind, each in needs.items():
        if not each or not calls[kind]:
            return None
        if abs(calls[kind] - len(each)) > 0.1 * len(each):
            return None
        least += sum(max(f / peaks["bf16_flops_per_s"],
                         b / peaks["hbm_bytes_per_s"]) for f, b in each) \
            * calls[kind] / len(each)
        total += spent[kind]
    return 100.0 * least / total if total else None
