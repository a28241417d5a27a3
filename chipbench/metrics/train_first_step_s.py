"""Mean seconds of the first call of each job's jitted train step in the
traced window (the program's ``repro.train.first_step`` spans): trace,
lower, compile or load from the compilation cache, and dispatch."""
from chipbench.program_spans import spans


def read(run):
    if run.red is None:
        return None
    found = spans(run.red, "repro.train.first_step")
    return sum(s.dur for s in found) / len(found) if found else None
