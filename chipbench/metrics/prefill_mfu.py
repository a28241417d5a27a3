"""Model FLOP utilization of the prefill programs, in %: the prefill FLOPs
of every batch they ran in the traced window (the body at every prompt
position, the head at the last, causal attention) over their summed device
time times the chip's bf16 peak."""
from chipbench.flops import prefill_flops


def read(run):
    if run.red is None:
        return None
    flops = spent = 0.0
    for p in run.traffic["cycle"]:
        runs = run.red.module_runs(f"prefill_{p}")
        flops += prefill_flops(run.model, run.data["batch"], int(p)) * len(runs)
        spent += sum(r.dur for r in runs)
    if spent <= 0:
        return None
    return 100.0 * flops / (spent * run.peak["bf16_flops"])
