"""Serving cell: the planted faults fail ``correct``, a sound run passes,
and the float8 control separates from the program."""
import pytest

from chipbench.tests import tiny
from chipbench.tests._faults import program_and_control, run_with


def test_a_sound_run_is_correct(tmp_path):
    r = run_with(tmp_path, tiny.SERVE)
    assert r["correct"], r["checks"]


@pytest.mark.parametrize("fault", ["token_altered"])
def test_a_planted_fault_is_not_correct(tmp_path, fault):
    r = run_with(tmp_path, tiny.SERVE, fault)
    assert not r["correct"], r["checks"]


def test_the_control_reads_three_times_the_program(tmp_path):
    got = program_and_control(tmp_path, tiny.SERVE)
    assert got["control"]["token_gap"] >= 3 * got["program"]["token_gap"]
    assert got["control"]["token_gap"] > 0
