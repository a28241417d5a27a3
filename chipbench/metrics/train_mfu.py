"""Model FLOP utilization of the train-step program, in %: the forward and
backward FLOPs of the steps it ran in the traced window (6 per matmul
parameter per token, output head included, plus causal attention; nothing
recomputed) over its summed device time on chip 0 times the cell's chips
times one chip's bf16 peak. On a mesh every chip runs each step at once, so
chip 0's program time is the step's."""
from chipbench.flops import train_step_flops


def read(run):
    if run.red is None:
        return None
    runs = run.red.module_runs("train_step")
    spent = sum(r.dur for r in runs)
    if not runs or spent <= 0:
        return None
    flops = train_step_flops(run.model, run.data["batch"], run.data["seq"]) * len(runs)
    return 100.0 * flops / (spent * run.cell.chips * run.peak["bf16_flops"])
