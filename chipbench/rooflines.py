"""Shares of the chip's peaks read from a reduced trace: utilization of a
compiled program, the roofline share of the flash-attention kernel, and the
share of a chip's busy time spent in collectives.

The flash kernel is found as a ``tpu_custom_call`` whose result and three
operands are 4-D (q [B, H, S, Dh], k and v [B, KV, S, Dh]); its shapes are
read from the operation's HLO text, so each call is costed at its own size.
"""
from __future__ import annotations

import re

from chipbench.flops import flash_fwd_cost, roofline_seconds
from chipbench.trace import Reduced, _clip, _short, _union, module_of, operand_shapes

# the HLO names of the operations that exchange data between chips; the TPU
# compiler turns some into generic ``async-collective-start``/``-done`` pairs
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|async-collective)")


def flash_calls(red: Reduced, functions: set[str]):
    """(seconds, (B, H, KV, S, Dh)) of each flash-attention kernel run in a
    run of one of ``functions``."""
    find, names = module_of(red), {f"jit_{f}" for f in functions}
    for o in red.ops:
        if 'custom_call_target="tpu_custom_call"' not in o.name or find(o) not in names:
            continue
        shapes = operand_shapes(o.name)
        if len(shapes) < 4 or any(len(s) != 4 for s in shapes[:4]):
            continue
        (b, h, s, dh), (_, kv, _, _) = shapes[0], shapes[2]
        yield o.dur, (b, h, kv, s, dh)


def flash_roofline_pct(red: Reduced, functions: set[str], peak: dict):
    """Least time over kernel time, in %, of every flash call in the runs of
    ``functions``; None where there is none. Also returns what bounds it."""
    ideal = spent = 0.0
    bounds = set()
    for dur, (b, h, kv, s, dh) in flash_calls(red, functions):
        t, bound = roofline_seconds(*flash_fwd_cost(b, h, kv, s, dh), peak)
        ideal += t
        spent += dur
        bounds.add(bound)
    if spent <= 0:
        return None, None
    return 100.0 * ideal / spent, "/".join(sorted(bounds))


def idle_pct(red: Reduced) -> float:
    return 100.0 * (1.0 - red.busy_s / red.window_s)


def collective_pct(red: Reduced) -> float | None:
    """Chip 0's time in collective operations over its busy time in the
    window, in %; None where it ran none."""
    lo, hi = red.window
    spans = [(o.start, o.start + o.dur) for o in red.ops]
    ours = [(o.start, o.start + o.dur) for o in red.ops if COLLECTIVE.match(_short(o.name))]
    if not ours:
        return None
    busy = sum(b - a for a, b in _union(_clip(spans, lo, hi)))
    spent = sum(b - a for a, b in _union(_clip(ours, lo, hi)))
    return 100.0 * spent / busy
