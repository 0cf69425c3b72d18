"""prefill_ms_per_ktok.serve: wall time of the window's
``engine.prefill_step`` spans over the prompt tokens they processed."""


def read(run):
    chunks = run.counters.get("chunks")
    if not chunks:
        return None
    lo, hi = run.window
    spent = sum(e - s for s, e in
                run.spans.within("engine.prefill_step", lo, hi))
    return 1e3 * spent / (sum(n for _, _, n in chunks) / 1e3)
