"""Roofline share of the decode step, in %: the HBM bytes each step must
read (every bf16 weight once, and the keys and values of the positions
before it) over its device time times the chip's HBM bandwidth.

A batch's steps sit at positions P .. P + G - 2 (prompt P, G tokens, the
first from prefill); the bytes grow linearly with the position, so the
steps of whole batches are costed at their mean position."""
from chipbench.flops import decode_step_bytes


def read(run):
    if run.red is None:
        return None
    gen, batch = run.data["gen"], run.data["batch"]
    moved = spent = 0.0
    for p in run.traffic["cycle"]:
        runs = run.red.module_runs(f"decode_{p}")
        mean_pos = int(p) + (gen - 2) / 2
        moved += decode_step_bytes(run.model, batch, mean_pos) * len(runs)
        spent += sum(r.dur for r in runs)
    if spent <= 0:
        return None
    return 100.0 * moved / (spent * run.peak["hbm_bytes_per_s"])
