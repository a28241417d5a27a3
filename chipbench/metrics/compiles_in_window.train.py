"""Backend compiles in the traced training window, cache loads included:
the program's ``repro.compile`` events there. None where the program wrote
no ``repro.train.segment`` span, so that a program without the events reads
as unmeasured, not as 0."""
from chipbench.program_spans import spans


def read(run):
    if run.red is None or not spans(run.red, "repro.train.segment"):
        return None
    return len(spans(run.red, "repro.compile"))
