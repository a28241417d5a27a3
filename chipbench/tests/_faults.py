"""Shared body of the fault tests: a run of a cell at smoke size on the
CPU, with the timed path broken underneath, must come out not correct; a
sound one correct; and the control must read at least three times what the
program reads."""
import os

from chipbench import calibrate, faults, harness
from chipbench.peaks import PEAKS
from chipbench.tests import tiny

SEED = 2**31 + 21


def run_with(tmp_path, cell, fault=None):
    root = tiny.make_root(str(tmp_path))
    if fault is None:
        return tiny.run(root, cell, seed=SEED)
    with faults.FAULTS[fault]():
        return tiny.run(root, cell, seed=SEED)


def program_and_control(tmp_path, cell):
    root = tiny.make_root(str(tmp_path))
    c = harness.Cell(cell, root=root)
    lines = calibrate.readings(c, SEED, "program", True, PEAKS["TPU v5 lite"],
                               os.path.join(root, "work"))
    return {line["what"]: line["readings"] for line in lines}
