"""Roofline share of the flash-attention forward kernel in prefill, in %:
the least time its calls could take at the chip's peaks, from their shapes,
over their device time in the trace."""
from chipbench.rooflines import flash_roofline_pct


def read(run):
    if run.red is None:
        return None
    names = {f"prefill_{p}" for p in run.traffic["cycle"]}
    return flash_roofline_pct(run.red, names, run.peak)[0]
