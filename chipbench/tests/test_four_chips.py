"""The four-chip training cell at smoke widths on four virtual CPU devices:
a sound run is correct, with every leaf of the last commit restored bit for
bit under the mesh shardings; the fault patch that leaves out the exchange
between chips changes nothing while the exchange is kept; each fault the
cell can have makes it not correct; and the float8 control separates from
the program.

The runs share one child process, started with four host devices (a process
fixes its device count when JAX starts)."""
import json
import os
import subprocess
import sys

import pytest

from chipbench.tests import tiny

CELL = "granite-3-2b.train-ckpt-4chip"
SEED = 2**31 + 21
FAULTS = ["state_unchanged", "half_batch", "exchange_left_out"]

CHILD = r"""
import contextlib, functools, json, os, sys

import jax

from chipbench import calibrate, faults
from chipbench.peaks import PEAKS
from chipbench.tests import tiny

root, cell, seed = sys.argv[1], sys.argv[2], int(sys.argv[3])
assert len(jax.devices()) == 4, jax.devices()
tiny.make_root(root, cells=[cell])
work = os.path.join(root, "work")
for case in sys.argv[4:]:
    if case == "control":
        from chipbench import harness

        lines = calibrate.readings(harness.Cell(cell, root=root), seed, "program", True,
                                   PEAKS["TPU v5 lite"], work)
        print(json.dumps({"case": case, "readings": {l["what"]: l["readings"] for l in lines}}))
        continue
    patch = {"sound": contextlib.nullcontext,
             "exchange_kept": functools.partial(faults.exchange_left_out, keep=True),
             **faults.FAULTS}[case]
    with patch():
        r = tiny.run(root, cell, seed=seed, work=work)
    print(json.dumps({"case": case, "correct": r["correct"], "checks": r["checks"],
                      "count": r["device"]["count"]}), flush=True)
"""


@pytest.fixture(scope="module")
def results(tmp_path_factory):
    root = tmp_path_factory.mktemp("four_chips")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=4").strip(),
               PYTHONPATH=os.pathsep.join([tiny.ROOT, os.path.join(tiny.ROOT, "src")]))
    cases = ["sound", "exchange_kept", *FAULTS, "control"]
    p = subprocess.run([sys.executable, "-c", CHILD, str(root), CELL, str(SEED), *cases],
                       env=env, cwd=tiny.ROOT, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    lines = [json.loads(x) for x in p.stdout.splitlines() if x.startswith("{")]
    return {line["case"]: line for line in lines}


def test_a_sound_run_on_four_devices_is_correct_and_restores_bit_for_bit(results):
    r = results["sound"]
    assert r["correct"] and r["count"] == 4, r["checks"]
    assert r["checks"]["ckpt_leaf_mismatch"]["value"] == 0
    assert r["checks"]["resume_breaks"]["value"] == 0


def test_the_exchange_patch_alone_changes_nothing(results):
    r = results["exchange_kept"]
    assert r["correct"], r["checks"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_is_not_correct(results, fault):
    r = results[fault]
    assert not r["correct"], r["checks"]


def test_the_control_reads_three_times_the_program(results):
    got = results["control"]["readings"]
    assert any(got["control"][k] >= 3 * got["program"][k] for k in got["program"])
