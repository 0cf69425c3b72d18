"""dispatch_ms.train: the host's time to hand a step to the device: median
of the program's ``GPTHybridTrainStep.step`` spans of the traced window
(the compiled call until it returns, not the step's device time)."""
from harness import program_spans as ps


def read(run):
    return ps.median_ms(ps.ms(s) for s in ps.named(
        ps.traced(run), "GPTHybridTrainStep.step"))
