"""The yardstick: peak table, FLOP and byte counts against a hand count, and
the trace reduction on a small synthetic trace."""
import pytest

from chipbench import flops, peaks, rooflines, trace
from chipbench.harness import load_json
from chipbench.tests.tiny import BENCH_DIR

import os


def _model(name):
    return flops.Dense.from_config(load_json(os.path.join(BENCH_DIR, "configs", name + ".json")))


def test_unknown_device_kind_raises():
    assert peaks.peak("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(ValueError):
        peaks.peak("TPU v9 imaginary")


def test_qwen3_counts_by_hand():
    m = flops.Dense(layers=28, hidden=1024, heads=16, kv_heads=8, head_dim=128,
                    ffn=3072, vocab=151936, qk_norm=True)
    # q 1024x2048, k and v 1024x1024, o 2048x1024, three 1024x3072 in the MLP
    per_layer = 2_097_152 + 2 * 1_048_576 + 2_097_152 + 3 * 3_145_728
    assert m.layer_matmul_params == per_layer == 15_728_640
    assert m.body_matmul_params + m.head_params == 28 * per_layer + 155_582_464
    # one token: 6 per parameter, attention over itself only (one pair)
    assert flops.train_step_flops(m, 1, 1) == 6 * 595_984_384 + 3 * 28 * 4 * 16 * 128
    cut = _model("qwen3-0.6b")
    assert cut.layers == 4 and cut.body_matmul_params == 4 * per_layer


def test_granite_counts_by_hand():
    m = _model("granite-3-2b")
    # q and o 2048x2048, k and v 2048x512, MLP 3 x 2048x8192
    assert m.layer_matmul_params == 2 * 4_194_304 + 2 * 1_048_576 + 3 * 16_777_216
    assert m.body_matmul_params == 2_432_696_320 and m.head_params == 2048 * 49155
    # prefill of one 2-token prompt: body twice, head once, 3 causal pairs
    assert flops.prefill_flops(m, 1, 2) == (2 * 2_432_696_320 * 2 + 2 * 2048 * 49155
                                            + 40 * 4 * 32 * 64 * 3)
    kv = 40 * 2 * 1 * 10 * 8 * 64 * 2  # positions 0..9, k and v, bf16
    weights = (2_432_696_320 + 2048 * 49155 + 40 * 2 * 2048 + 2048) * 2
    assert flops.decode_step_bytes(m, 1, 9) == weights + kv
    f, b = flops.flash_fwd_cost(2, 16, 8, 2048, 128)
    assert f == 4 * 16 * 128 * 2 * 2048 * 2049 // 2
    assert b == (2 * 2 * 16 * 2048 * 128 + 2 * 2 * 8 * 2048 * 128) * 2
    assert flops.roofline_seconds(f, b, peaks.peak("TPU v5 lite"))[1] == "compute"


FLASH = ('%attn.1 = bf16[2,16,2048,128]{3,2,1,0} custom-call(bf16[2,16,2048,128]{3,2,1,0} %a, '
         'bf16[2,8,2048,128]{3,2,1,0} %b, bf16[2,8,2048,128]{3,2,1,0} %c), '
         'custom_call_target="tpu_custom_call"')
S = 1_000_000_000  # ns


def _planes():
    ops = [("%fusion.1 = f32[8] fusion()", 0, S), ("%fusion.2 = f32[8] fusion()", S // 2, S),
           (FLASH, 3 * S, S), ("%copy.3 = f32[8] copy()", 9 * S, 2 * S)]
    mods = [("jit_train_step(12)", 0, 2 * S), ("jit_train_step(12)", 3 * S, S)]
    host = [("bench.window", 0, 5 * S), ("bench.segment", 0, 5 * S),
            ("bench.ckpt.save", int(2.2 * S), int(0.6 * S)), ("not.ours", 0, 9 * S)]
    return [("/device:TPU:0", [("XLA Ops", ops), ("XLA Modules", mods),
                               ("Steps", [("x", 0, 9 * S)])]),
            ("/device:TPU:1", [("XLA Ops", [(FLASH, 0, 5 * S)])]),
            ("/host:CPU", [("python", host)])]


def test_busy_union_idle_share_and_kernel_time():
    red = trace.reduce_planes(_planes())
    assert red.window_s == pytest.approx(5.0) and red.chips == 2
    # chip 0: [0, 1.5] and [3, 4] inside the window, chip 1: all of it
    assert red.busy_s == pytest.approx((2.5 + 5.0) / 2)
    assert sum(m.dur for m in red.module_runs("train_step")) == pytest.approx(3.0)
    assert rooflines.idle_pct(red) == pytest.approx(25.0)
    calls = list(rooflines.flash_calls(red, {"train_step"}))
    assert calls == [(1.0, (2, 16, 8, 2048, 128))]
    share, bound = rooflines.flash_roofline_pct(red, {"train_step"}, peaks.peak("TPU v5 lite"))
    want = flops.roofline_seconds(*flops.flash_fwd_cost(2, 16, 8, 2048, 128),
                                  peaks.peak("TPU v5 lite"))[0]
    assert share == pytest.approx(100 * want) and bound == "compute"
    assert rooflines.flash_roofline_pct(red, {"decode_512"}, peaks.peak("TPU v5 lite")) == (None, None)


def test_idle_gaps_go_to_the_innermost_span():
    red = trace.reduce_planes(_planes())
    assert red.gaps == [(1.5, 3.0), (4.0, 5.0)]
    b = trace.breakdown(red)
    assert dict(b["idle_gaps"]) == pytest.approx({"bench.ckpt.save": 1.5, "bench.segment": 1.0})
    ops = dict(b["device_ops"])
    assert ops["jit_train_step:attn.1"] == pytest.approx(1.0)
    assert len(b["device_ops"]) <= 10 and "jit_train_step:fusion.1" in ops


def test_a_trace_without_window_or_device_is_refused():
    planes = _planes()
    with pytest.raises(ValueError):
        trace.reduce_planes([p for p in planes if not p[0].startswith("/device")])
    with pytest.raises(ValueError):
        trace.reduce_planes([p for p in planes if not p[0].startswith("/host")])


def test_device_ops_count_their_own_time():
    """A loop's event holds its body's events: the breakdown charges each
    operation its own time, so the totals add up to the busy time."""
    ops = [trace.Op("%while.1 = while()", 0.0, 10.0), trace.Op("%fusion.2 = fusion()", 1.0, 2.0),
           trace.Op("%conv.3 = convolution()", 4.0, 5.0), trace.Op("%fusion.4 = fusion()", 5.0, 1.0),
           trace.Op("%copy.5 = copy()", 11.0, 1.0)]
    own = {o.name.split(" ")[0]: t for o, t in trace.self_times(ops)}
    assert own == pytest.approx({"%while.1": 3.0, "%fusion.2": 2.0, "%conv.3": 4.0,
                                 "%fusion.4": 1.0, "%copy.5": 1.0})
