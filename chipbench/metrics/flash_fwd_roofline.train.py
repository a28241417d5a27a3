"""Roofline share of the flash-attention forward kernel in the train step,
in %: the least time its calls could take at the chip's peaks, from their
shapes, over their device time in the trace."""
from chipbench.rooflines import flash_roofline_pct


def read(run):
    if run.red is None:
        return None
    return flash_roofline_pct(run.red, {"train_step"}, run.peak)[0]
