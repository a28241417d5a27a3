"""Mean seconds per checkpoint save in the traced window that the training
loop spent copying the state from the device to the host (the program's
``repro.ckpt.snapshot`` spans)."""
from chipbench.program_spans import spans


def read(run):
    if run.red is None:
        return None
    found = spans(run.red, "repro.ckpt.snapshot")
    return sum(s.dur for s in found) / len(found) if found else None
