"""Mean seconds a job in the traced window took to resume: from the start of
the program's ``repro.ckpt.restore`` span to the end of its
``repro.ckpt.restore.ready`` span (same ``step``), which opens as the
restore returns and closes once every restored leaf is ready on the device."""
from chipbench.program_spans import spans


def read(run):
    if run.red is None:
        return None
    ready = spans(run.red, "repro.ckpt.restore.ready")
    times = []
    for r in spans(run.red, "repro.ckpt.restore"):
        ends = [s.start + s.dur for s in ready
                if s.attrs.get("step") == r.attrs.get("step") and s.start >= r.start]
        if "step" in r.attrs and ends:
            times.append(min(ends) - r.start)
    return sum(times) / len(times) if times else None
