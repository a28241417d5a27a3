"""Model FLOP utilization of a hybrid model's prefill programs, in %: the
prefill FLOPs of every batch they ran in the traced window (the body's
matmuls at every prompt position, the head at the last, causal attention
of the attention layers, the SSD scan of the Mamba-2 layers) over their
summed device time times the chip's bf16 peak. None for a model that is
not a ``hybrid_costs.Hybrid``."""
from chipbench.hybrid_costs import Hybrid, prefill_flops


def read(run):
    if run.red is None or not isinstance(run.model, Hybrid):
        return None
    flops = spent = 0.0
    for p in run.traffic["cycle"]:
        runs = run.red.module_runs(f"prefill_{p}")
        flops += prefill_flops(run.model, run.data["batch"], int(p)) * len(runs)
        spent += sum(r.dur for r in runs)
    if spent <= 0:
        return None
    return 100.0 * flops / (spent * run.peak["bf16_flops"])
