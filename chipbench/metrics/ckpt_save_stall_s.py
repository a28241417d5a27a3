"""Seconds the training loop waited per checkpoint save: the jobs'
``SegmentResult.save_s`` over the saves they made (the program's own timer
around its save calls and its final wait)."""


def read(run):
    segs = run.data.get("segments")
    if not segs or not run.data.get("saves"):
        return None
    return sum(r.save_s for r in segs) / run.data["saves"]
