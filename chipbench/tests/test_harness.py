"""The harness finds every cell's files by name, makes the same traffic from
the same seed, refuses to run without a TPU, and takes a new cell,
configuration or metric added as files alone."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from chipbench import harness
from chipbench.kinds import serve_closed
from chipbench.references import dense_gqa as R
from chipbench.tests import tiny

BENCH = harness.load_benchmark()


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_names_existing_files(name):
    cell = harness.Cell(name)
    assert cell.config["name"] == cell.workload["config"]
    assert hasattr(cell.kind, "Driver") and hasattr(cell.reference, "Model")
    assert set(cell.readers) == {m["name"] for m in cell.per_layer} != set()
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2
    for m in cell.per_layer:
        assert m["moves"] in {e["name"] for e in cell.end_to_end}
    # the cell's smoke files, and the numbers its smoke runs judge
    assert tiny.has_smoke(cell.workload), f"{name}: no smoke files under chipbench/tests/smoke"
    assert set(cell.limits) == set(tiny.smoke("limits", name))


def test_every_configuration_states_what_the_contract_asks():
    for c in BENCH["configs"]:
        cfg = json.load(open(os.path.join(tiny.ROOT, c["file"])))
        assert cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        widths = ("hidden_size", "intermediate_size", "head_dim", "num_attention_heads",
                  "num_key_value_heads")
        assert not set(c["reduced"]) & set(widths)


def test_serving_traffic_is_the_same_per_seed():
    t = json.load(open(os.path.join(tiny.BENCH_DIR, "traffic", "serve-from-commit.json")))
    big = 2**31 + 12345
    a, b = serve_closed.schedule(t, big, 3), serve_closed.schedule(t, big, 3)
    assert a == b and a != serve_closed.schedule(t, big + 1, 3)
    per_cycle = sum(t["cycle"].values())
    want = sorted(int(p) for p, n in t["cycle"].items() for _ in range(n))
    for i in range(3):  # every seed serves the same lengths, in its own order
        assert sorted(a[i * per_cycle:(i + 1) * per_cycle]) == want
    p1 = serve_closed.prompts(t, big, 4, 512, 49155)
    assert np.array_equal(p1, serve_closed.prompts(t, big, 4, 512, 49155))
    assert not np.array_equal(p1, serve_closed.prompts(t, big + 1, 4, 512, 49155))
    assert p1.shape == (t["batch"], 512) and p1.max() < 49155
    s = serve_closed.sample(t, big, a)
    assert s == serve_closed.sample(t, big, a) and a[s[0][0]] == max(a)


def test_training_traffic_is_the_programs_per_seed():
    from repro.data.tokens import SyntheticTokens

    big = 2**31 + 77
    ds = SyntheticTokens(151936, 2048, 4, seed=big)
    for step in (0, 2, 301):
        want = ds.shard_batch_at(step, 0, 1)
        assert np.array_equal(R.synthetic_batch(big, step, 151936, 4, 2048), want)
    assert not np.array_equal(R.synthetic_batch(big + 1, 0, 151936, 4, 2048),
                              ds.shard_batch_at(0, 0, 1))


def _run_py(root, *args):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, "chipbench/run.py", *args], cwd=root,
                          env=env, capture_output=True, text=True, timeout=120)


def _no_result(proc):
    last = proc.stdout.strip().splitlines()[-1:] or [""]
    return not last[0].startswith("{")


def test_a_run_without_a_tpu_fails_and_prints_no_result():
    p = _run_py(tiny.ROOT, "--workload", BENCH["workloads"][0]["name"], "--seed", "1",
                "--seconds", "1", "--trace", "0")
    assert p.returncode != 0 and _no_result(p), p.stderr[-2000:]
    assert "no TPU" in p.stderr


def test_a_bare_checkout_fails_and_prints_no_result(tmp_path):
    shutil.copy(os.path.join(tiny.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(tiny.BENCH_DIR, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", ".work"))
    p = _run_py(tmp_path, "--workload", BENCH["workloads"][0]["name"], "--seed", "1",
                "--seconds", "1", "--trace", "0")
    assert p.returncode != 0 and _no_result(p)


def test_a_new_cell_configuration_and_metric_are_files_only(tmp_path):
    """Adds, as files only, a configuration, a traffic mix, the cell of the
    two, and a per-layer metric with its reader; the harness finds them all
    and runs the cell."""
    root = tiny.make_root(str(tmp_path))
    bench = tiny.load(os.path.join(root, "BENCHMARK.json"))
    cfg = tiny.load(os.path.join(root, "chipbench/configs/qwen3-0.6b.json"))
    cfg["name"] = "qwen3-0.6b-copy"
    tiny.dump(os.path.join(root, "chipbench/configs/qwen3-0.6b-copy.json"), cfg)
    bench["configs"].append({"name": "qwen3-0.6b-copy", "source": cfg["source"],
                             "file": "chipbench/configs/qwen3-0.6b-copy.json",
                             "reduced": ["num_hidden_layers"], "why": "a copy"})
    traffic = tiny.load(os.path.join(root, "chipbench/traffic/serve-from-commit.json"))
    traffic["cycle"] = {"64": 1}
    tiny.dump(os.path.join(root, "chipbench/traffic/serve-short.json"), traffic)
    cell = "qwen3-0.6b-copy.serve-short"
    bench["workloads"].append({"name": cell, "config": "qwen3-0.6b-copy",
                               "traffic": "serve-short", "chips": 1, "why": "a new cell"})
    tiny.dump(os.path.join(root, f"chipbench/limits/{cell}.json"), {"token_gap": 1.0})
    for m in bench["end_to_end"]:
        if m["name"] in ("ttft_p95_ms", "itl_p95_ms"):
            m["workloads"].append(cell)
    bench["per_layer"].append({"name": "served_requests", "unit": "requests",
                               "better": "higher", "source": "program_counter",
                               "layer": "decode step", "moves": "itl_p95_ms"})
    with open(os.path.join(root, "chipbench/metrics/served_requests.py"), "w") as f:
        f.write("def read(run):\n    return run.data.get('requests')\n")
    tiny.dump(os.path.join(root, "BENCHMARK.json"), bench)

    found = harness.Cell(cell, root=root)
    assert found.config["name"] == "qwen3-0.6b-copy"
    assert "served_requests" in found.readers
    assert "served_requests" in harness.Cell(tiny.SERVE, root=root).readers
    assert "served_requests" not in harness.Cell(tiny.TRAIN, root=root).readers
    result = tiny.run(root, cell, seed=2**31 + 3)
    assert result["correct"] and result["attempted"] == traffic["batch"]
    assert set(result["metrics"]) == {"ttft_p95_ms", "itl_p95_ms", "setup_s"}
    assert list(result)[-1] == "checks"


def test_a_step_traced_from_two_call_sites_is_one_program():
    """The train step, lowered for the TPU with its Pallas kernels from two
    call sites (as the set-up job and the window trace it), is two programs
    to the compilation cache while locations keep Python frames, and one
    once the harness has taken them out."""
    import re

    import jax
    import jax.numpy as jnp

    from repro import configs
    from repro.kernels import ops
    from repro.models import transformer as T
    from repro.models.params import abstract_params
    from repro.optim.adamw import AdamW
    from repro.train.loop import jit_train_step

    cfg = configs.get("qwen3_0_6b").replace(n_layers=1, use_pallas="on")
    opt = AdamW(lr=1e-3)
    params = abstract_params(T.param_defs(cfg), jnp.bfloat16)
    args = (params, jax.eval_shape(opt.init, params),
            {"tokens": jax.ShapeDtypeStruct((1, 256), jnp.int32)})

    def kernels(traced):
        text = traced.lower(lowering_platforms=("tpu",)).as_text(debug_info=False)
        found = re.findall(r"tpu_custom_call.*", text)
        assert found
        return found

    def from_setup():
        return kernels(jit_train_step(cfg, None, opt)[0].trace(*args))

    def from_window():
        return kernels(jit_train_step(cfg, None, opt)[0].trace(*args))

    was = jax.config.jax_traceback_in_locations_limit
    interpret = ops._interpret
    ops._interpret = lambda flag: False  # the TPU kernels, lowered without a TPU
    try:
        jax.config.update("jax_traceback_in_locations_limit", 10)  # JAX's default
        assert from_setup() != from_window()
        harness.one_program_per_call()
        assert from_setup() == from_window()
    finally:
        ops._interpret = interpret
        jax.config.update("jax_traceback_in_locations_limit", was)
