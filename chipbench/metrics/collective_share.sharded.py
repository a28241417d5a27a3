"""Share of chip 0's busy time in the traced window spent in collective
operations, in %: the device time of its all-reduce, all-gather,
reduce-scatter, collective-permute and all-to-all operations (their start
and done halves included, and the compiler's generic async collectives)
over the union of its operation intervals in the window.
What an asynchronous collective moves while other operations run is not in
the numerator; only the time the chip spends in the operations themselves."""
from chipbench.rooflines import collective_pct


def read(run):
    return None if run.red is None else collective_pct(run.red)
