"""The Mamba-2 / attention hybrid's cell: its cost functions at the
published sizes, its three readers on a small synthetic trace, and its
``correct`` at smoke size on the CPU (a sound run passes, a decode that
loses its state at one step fails, the float8 control reads at least three
times the program)."""
import os
from types import SimpleNamespace

import pytest

from chipbench import flops, hybrid_costs, peaks, trace
from chipbench.harness import _module_at, load_json, patched
from chipbench.tests import tiny
from chipbench.tests._faults import program_and_control, run_with

CELL = "granite-4.0-h-micro.serve-decode-heavy"
S = 1_000_000_000  # ns
PEAK = peaks.peak("TPU v5 lite")


def _model():
    return hybrid_costs.Hybrid.from_config(
        load_json(os.path.join(tiny.ROOT, "chipbench/configs/granite-4.0-h-micro.json")))


def test_costs_at_the_published_sizes():
    m = _model()
    assert (m.layers, m.attn_layers, m.mamba_layers, m.chunk) == (40, 4, 36, 256)
    assert m.params == 3_191_396_096
    # a decode step at batch 32 and about the cycle's mean position: 6.38 GB
    # of weights, 2 x 2.45 GB of states and tails, 0.38 GB of K/V; the
    # Mamba-2 states, tails and mixer weights are 58 % of it
    moved = hybrid_costs.decode_step_bytes(m, 32, 1450)
    state = 36 * 2 * 32 * (64 * 64 * 128 * 4 + 3 * 4352 * 2)
    kv = 4 * 2 * 32 * 1451 * 8 * 64 * 2
    assert moved == 3_191_396_096 * 2 + state + kv
    assert 11.6e9 < moved < 11.7e9
    assert (state + 25_847_232 * 36 * 2) / moved == pytest.approx(0.58, abs=0.01)


def test_ssd_cost_by_hand():
    """One chunk of 4 positions, one group of state 2, two heads of 3."""
    f, b = hybrid_costs.ssd_fwd_cost(1, 4, 2, 3, 1, 2, 4)
    pairs = 10
    assert f == 2 * pairs * 2 + 2 * (2 * pairs * 3 + 4 * 4 * 3 * 2)
    assert b == 2 * 4 * 2 * 3 * 2 + 2 * 2 * 4 * 4 + 2 * 4 * 2 * 2 + 2 * 2 * 3 * 2 * 4


def test_prefill_flops_add_the_ssd_to_the_matmuls():
    m = _model()
    body = 2 * m.body_matmul_params * 32 * 2048
    ssd, _ = hybrid_costs.ssd_fwd_cost(32, 2048, 64, 64, 1, 128, 256)
    total = hybrid_costs.prefill_flops(m, 32, 2048)
    assert total == (body + 2 * 2048 * 100352 * 32
                     + 4 * 4 * 32 * 64 * flops.causal_pairs(32, 2048) + 36 * ssd)
    assert 0.015 < 36 * ssd / total < 0.025  # the SSD is about 2 % of prefill


SSD_OP = ('%ssd_fwd.1 = (bf16[32,2048,4096]{2,1,0}, f32[32,64,64,128]{3,2,1,0}) '
          'custom-call(bf16[32,2048,4096]{2,1,0} %x, f32[32,64,2048]{2,1,0} %d, '
          'f32[32,64,2048]{2,1,0} %c, bf16[32,2048,128]{2,1,0} %b, '
          'bf16[32,2048,128]{2,1,0} %cc, f32[32,64,64,128]{3,2,1,0} %h), '
          'custom_call_target="tpu_custom_call"')
FLASH_OP = ('%flash_fwd.2 = bf16[32,32,2048,64]{3,2,1,0} custom-call(bf16[32,32,2048,64] %q, '
            'bf16[32,8,2048,64] %k, bf16[32,8,2048,64] %v), custom_call_target="tpu_custom_call"')


def _planes():
    ops = [(SSD_OP, S // 10, S // 100), (SSD_OP, S // 5, S // 100),
           (FLASH_OP, S // 2, S // 50), ("%fusion.3 = f32[8] fusion()", 2 * S, S)]
    mods = [("jit_prefill_2048(7)", 0, S), ("jit_decode_2048(8)", 2 * S, S),
            ("jit_decode_2048(8)", 3 * S + S // 2, S)]
    return [("/device:TPU:0", [("XLA Ops", ops), ("XLA Modules", mods)]),
            ("/host:CPU", [("python", [("bench.window", 0, 5 * S)])])]


def _run(model):
    return SimpleNamespace(red=trace.reduce_planes(_planes()), peak=PEAK, model=model,
                           traffic={"cycle": {"2048": 1}},
                           data={"batch": 32, "gen": 512})


def _reader(name):
    return _module_at(os.path.join(tiny.BENCH_DIR, "metrics", name + ".py"),
                      "test_hybrid_" + name.replace(".", "_")).read


def test_the_readers_cost_their_programs():
    m = _model()
    run = _run(m)
    t, _ = flops.roofline_seconds(*hybrid_costs.ssd_fwd_cost(32, 2048, 64, 64, 1, 128, 256),
                                  PEAK)
    assert _reader("ssd_fwd_roofline.serve")(run) == pytest.approx(100 * 2 * t / (2 * S / 100 * 1e-9))
    assert _reader("prefill_mfu.hybrid")(run) == pytest.approx(
        100 * hybrid_costs.prefill_flops(m, 32, 2048) / PEAK["bf16_flops"])
    assert _reader("decode_roofline.hybrid")(run) == pytest.approx(
        100 * 2 * hybrid_costs.decode_step_bytes(m, 32, 2048 + 255) / (2 * PEAK["hbm_bytes_per_s"]))
    # the SSD call is not taken for flash; the flash call is
    from chipbench.rooflines import flash_calls
    assert [c[1] for c in flash_calls(run.red, {"prefill_2048"})] == [(32, 32, 8, 2048, 64)]


def test_the_readers_are_silent_on_another_model():
    from chipbench.tests.test_yardstick import _model as dense

    run = _run(dense("granite-3-2b"))
    for name in ("ssd_fwd_roofline.serve", "prefill_mfu.hybrid", "decode_roofline.hybrid"):
        assert _reader(name)(run) is None, name


def test_a_sound_run_is_correct(tmp_path):
    r = run_with(tmp_path, CELL)
    assert r["correct"], r["checks"]


def _state_lost(leaf):
    """The decode step zeroes every Mamba-2 layer's ``leaf`` (state or conv
    tail) at one step of each request: the third (the smoke prompts are
    multiples of 64, and a request decodes fewer than 64 tokens)."""
    import jax.numpy as jnp

    from repro.train import steps

    def make(orig):
        def make_decode_step(*a, **k):
            step = orig(*a, **k)

            def broken(params, caches, token, pos):
                def lose(c):
                    if "h" not in c:
                        return c
                    return {**c, leaf: jnp.where(pos % 64 == 2, 0, c[leaf]).astype(c[leaf].dtype)}
                return step(params, {k: lose(c) for k, c in caches.items()}, token, pos)
            return broken
        return make_decode_step
    return patched(steps, "make_decode_step", make)


@pytest.mark.parametrize("leaf", ["h", "conv"])
def test_a_decode_that_loses_its_state_is_not_correct(tmp_path, leaf):
    root = tiny.make_root(str(tmp_path), cells=[CELL])
    with _state_lost(leaf):
        r = tiny.run(root, CELL, seed=2**31 + 21)
    assert not r["correct"], r["checks"]


def test_the_control_reads_three_times_the_program(tmp_path):
    got = program_and_control(tmp_path, CELL)
    assert got["control"]["token_gap"] >= 3 * got["program"]["token_gap"]
    assert got["control"]["token_gap"] > 0
