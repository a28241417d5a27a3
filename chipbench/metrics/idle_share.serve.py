"""Share of the traced serving window in which no operation ran on the
device, in %: one minus the union of operation intervals over the window."""
from chipbench.rooflines import idle_pct


def read(run):
    return None if run.red is None else idle_pct(run.red)
