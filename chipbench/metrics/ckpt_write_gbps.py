"""Rate at which the checkpoint layer wrote commits in the traced window, in
GB/s: the ``bytes`` of the program's ``repro.ckpt.write`` spans (serialising
the leaves, annex ingest and the commit) over their summed seconds."""
from chipbench.program_spans import spans


def read(run):
    if run.red is None:
        return None
    found = spans(run.red, "repro.ckpt.write")
    spent = sum(s.dur for s in found)
    if spent <= 0:
        return None
    return sum(s.attrs["bytes"] for s in found) / spent / 1e9
