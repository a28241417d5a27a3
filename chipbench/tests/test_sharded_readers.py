"""The readers of the four-chip cell on a small synthetic trace: the share of
chip 0's busy time in collectives, and the step's utilization of all the
chips it runs on."""
import os
from types import SimpleNamespace

import pytest

from chipbench import flops, peaks, rooflines, trace
from chipbench.harness import _module_at, load_json
from chipbench.tests.tiny import BENCH_DIR

S = 1_000_000_000  # ns


def _planes(collectives=True):
    ops = [("%fusion.1 = f32[8] fusion()", 0, 2 * S),
           ("%fusion.6 = f32[8] fusion()", 4 * S, S)]
    if collectives:
        ops += [("%all-gather-start.1 = (s32[8]) all-gather-start()", 2 * S, S // 2),
                ("%collective-permute-done.3 = f32[8] collective-permute-done()",
                 2 * S + S // 2, S // 2),
                ("%all-to-all.2 = bf16[8] all-to-all()", 3 * S, S // 2),
                ("%async-collective-done.4 = f32[8] async-collective-done()",
                 3 * S + S // 2, S // 2)]
    mods = [("jit_train_step(3)", 0, 2 * S), ("jit_train_step(3)", 3 * S, 2 * S)]
    host = [("bench.window", 0, 5 * S)]
    return [("/device:TPU:0", [("XLA Ops", ops), ("XLA Modules", mods)]),
            ("/device:TPU:1", [("XLA Ops", [("%fusion.1 = f32[8] fusion()", 0, 5 * S)])]),
            ("/host:CPU", [("python", host)])]


def _reader(name):
    return _module_at(os.path.join(BENCH_DIR, "metrics", name + ".py"),
                      "test_" + name.replace(".", "_")).read


def test_collective_share_is_chip_zeros_time_in_collectives_over_its_busy_time():
    red = trace.reduce_planes(_planes())
    assert rooflines.collective_pct(red) == pytest.approx(100 * 2.0 / 5.0)
    read = _reader("collective_share.sharded")
    assert read(SimpleNamespace(red=red)) == pytest.approx(40.0)
    # a trace with no collective reads as nothing to read, not as 0
    assert read(SimpleNamespace(red=trace.reduce_planes(_planes(collectives=False)))) is None
    assert read(SimpleNamespace(red=None)) is None


def test_sharded_utilization_divides_by_every_chip():
    red = trace.reduce_planes(_planes())
    model = flops.Dense.from_config(load_json(os.path.join(BENCH_DIR, "configs",
                                                           "granite-3-2b-4l.json")))
    pk = peaks.peak("TPU v5 lite")
    run = SimpleNamespace(red=red, model=model, peak=pk, cell=SimpleNamespace(chips=4),
                          data={"batch": 8, "seq": 4096})
    want = 100 * 2 * flops.train_step_flops(model, 8, 4096) / (4.0 * 4 * pk["bf16_flops"])
    read = _reader("train_mfu")
    assert read(run) == pytest.approx(want)
    # the same trace read for one chip is four times as high
    assert read(SimpleNamespace(red=red, model=model, peak=pk, cell=SimpleNamespace(chips=1),
                                data={"batch": 8, "seq": 4096})) == pytest.approx(4 * want)
    assert read(SimpleNamespace(red=None)) is None
