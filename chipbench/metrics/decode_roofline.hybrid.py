"""Roofline share of a hybrid model's decode step, in %: the HBM bytes
each step must move (every bf16 weight once, the keys and values of the
positions before it in the attention layers, each Mamba-2 layer's fp32
state and conv tail read and written once) over its device time times the
chip's HBM bandwidth. The steps of whole batches are costed at their mean
position, as ``decode_roofline`` costs them. None for a model that is not a
``hybrid_costs.Hybrid``."""
from chipbench.hybrid_costs import Hybrid, decode_step_bytes


def read(run):
    if run.red is None or not isinstance(run.model, Hybrid):
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
