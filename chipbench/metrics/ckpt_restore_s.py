"""Mean seconds a job spent restoring the newest checkpoint commit
(``SegmentResult.restore_s``, the program's own timer). The timer stops
before each leaf's transfer to the device has finished."""


def read(run):
    segs = run.data.get("segments")
    if not segs:
        return None
    return sum(r.restore_s for r in segs) / len(segs)
