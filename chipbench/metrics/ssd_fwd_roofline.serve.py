"""Roofline share of the SSD scan kernel (``ssd_fwd``) in prefill, in %:
the least time its calls could take at the chip's peaks, each costed from
its own HLO shapes (y [B, S, H * P], the final state [B, H, P, N], and B
[B, S, G * N] among the operands) and the model's chunk, over their device
time in the trace. None where no call ran, or for a model that is not a
``hybrid_costs.Hybrid``."""
from chipbench.flops import roofline_seconds
from chipbench.hybrid_costs import Hybrid, ssd_fwd_cost
from chipbench.trace import _short, module_of, operand_shapes


def ssd_calls(red, functions: set[str]):
    """(seconds, (B, S, H, P, G, N)) of each SSD kernel run in a run of one
    of ``functions``."""
    find, names = module_of(red), {f"jit_{f}" for f in functions}
    for o in red.ops:
        if (not _short(o.name).startswith("ssd_fwd")
                or 'custom_call_target="tpu_custom_call"' not in o.name
                or find(o) not in names):
            continue
        shapes = operand_shapes(o.name)
        (b, s, _), (_, h, p, n) = shapes[0], shapes[1]
        g = next((x[2] // n for x in shapes[2:]
                  if len(x) == 3 and x[:2] == (b, s) and x[2] != h * p), 1)
        yield o.dur, (b, s, h, p, g, n)


def read(run):
    if run.red is None or not isinstance(run.model, Hybrid):
        return None
    ideal = spent = 0.0
    names = {f"prefill_{p}" for p in run.traffic["cycle"]}
    for dur, (b, s, h, p, g, n) in ssd_calls(run.red, names):
        ideal += roofline_seconds(*ssd_fwd_cost(b, s, h, p, g, n, run.model.chunk),
                                  run.peak)[0]
        spent += dur
    return 100.0 * ideal / spent if spent > 0 else None
