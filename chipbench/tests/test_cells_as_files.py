"""A cell is added as files and list entries alone, and the benchmark's own
tests take it: on a copy of the benchmark, a new configuration, traffic mix
and cell are added with their smoke files, and the harness, reference and
fault tests all pass, the new cell's and configuration's cases among them; a
cell added without smoke files fails only its own case of
``test_every_cell_names_existing_files``.

Each copy runs the benchmark's tests in a child pytest, as a later change
that adds those files would run them."""
import os
import re
import shutil
import subprocess
import sys

from chipbench.tests import tiny

CONFIG = "qwen3-0.6b-copy"
TRAFFIC = "train-ckpt-short"
CELL = f"{CONFIG}.{TRAFFIC}"
# the reference's cases of each configuration (the existing configurations'
# run in the suite itself)
REFERENCE_CASES = ("test_training_init_is_the_programs", "test_logits_loss_and_gradients",
                   "test_prefill_then_decode")
TESTS = ["chipbench/tests/test_harness.py", "chipbench/tests/test_reference.py",
         "chipbench/tests/test_faults_train.py", "chipbench/tests/test_faults_serve.py"]


def _copy(dst) -> str:
    """A copy of the benchmark as a checkout holds it."""
    dst = str(dst)
    os.makedirs(dst)
    shutil.copy(os.path.join(tiny.ROOT, "BENCHMARK.json"), dst)
    shutil.copytree(tiny.BENCH_DIR, os.path.join(dst, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__", ".work"))
    return dst


def _add(src: str, config: str, traffic: str, cell: str, smoke: bool) -> None:
    """Adds ``config`` (a copy of qwen3-0.6b's file, unless it is that one),
    the traffic mix ``traffic`` (the training mix at half the sequence) and
    their cell, with its limits and list entries, and with ``smoke`` their
    smoke files; no file that is there is changed but ``BENCHMARK.json``'s
    lists."""
    path = os.path.join(src, "BENCHMARK.json")
    bench = tiny.load(path)
    if config not in {c["name"] for c in bench["configs"]}:
        cfg = tiny.load(os.path.join(src, "chipbench/configs/qwen3-0.6b.json"))
        cfg["name"] = config
        tiny.dump(os.path.join(src, f"chipbench/configs/{config}.json"), cfg)
        bench["configs"].append({"name": config, "source": cfg["source"],
                                 "file": f"chipbench/configs/{config}.json",
                                 "reduced": ["num_hidden_layers"], "why": "a copy"})
        if smoke:
            tiny.dump(tiny.smoke_path("configs", config, src),
                      tiny.smoke("configs", "qwen3-0.6b", src))
    t = tiny.load(os.path.join(src, "chipbench/traffic/train-ckpt.json"))
    t["seq_len"] //= 2
    tiny.dump(os.path.join(src, f"chipbench/traffic/{traffic}.json"), t)
    bench["workloads"].append({"name": cell, "config": config, "traffic": traffic,
                               "chips": 1, "why": "a cell added as files"})
    tiny.dump(os.path.join(src, f"chipbench/limits/{cell}.json"),
              tiny.load(os.path.join(src, "chipbench/limits/qwen3-0.6b.train-ckpt.json")))
    if smoke:
        tiny.dump(tiny.smoke_path("traffic", traffic, src), tiny.smoke("traffic", "train-ckpt", src))
        tiny.dump(tiny.smoke_path("limits", cell, src),
                  tiny.smoke("limits", "qwen3-0.6b.train-ckpt", src))
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("train_tokens_per_s", "ckpt_save_stall_s"):
            m["workloads"].append(cell)
    tiny.dump(path, bench)


def _pytest(src: str, *args: str):
    """(exit code, the PASSED and FAILED test ids) of the copy's tests."""
    env = {k: v for k, v in os.environ.items() if not k.startswith("PYTEST_")}
    env.update(JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([src, os.path.join(tiny.ROOT, "src")]))
    p = subprocess.run([sys.executable, "-m", "pytest", "-q", "-rA", "-p", "no:cacheprovider",
                        "-p", "no:randomly", *args], cwd=src, env=env,
                       capture_output=True, text=True, timeout=900)
    got = {k: set(re.findall(rf"^{k} (\S+)", p.stdout, re.M)) for k in ("PASSED", "FAILED")}
    return p.returncode, got, p.stdout[-4000:]


def test_a_cell_added_with_its_smoke_files_is_taken_by_every_test(tmp_path):
    src = _copy(tmp_path / "src")
    _add(src, CONFIG, TRAFFIC, CELL, smoke=True)
    # the cell runs, built from its smoke files like the others
    root = tiny.make_root(str(tmp_path / "root"), src=src)
    result = tiny.run(root, CELL, seed=2**31 + 5)
    assert result["correct"], result["checks"]
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    rc, got, out = _pytest(src, *[t for t in TESTS if "reference" not in t],
                           *(f"chipbench/tests/test_reference.py::{case}[{CONFIG}]"
                             for case in REFERENCE_CASES))
    assert rc == 0 and not got["FAILED"], out
    assert f"chipbench/tests/test_harness.py::test_every_cell_names_existing_files[{CELL}]" \
        in got["PASSED"]
    for case in REFERENCE_CASES:
        assert f"chipbench/tests/test_reference.py::{case}[{CONFIG}]" in got["PASSED"], out
    assert any("test_faults_train.py" in t for t in got["PASSED"])
    assert any("test_faults_serve.py" in t for t in got["PASSED"])


def test_a_cell_without_smoke_files_fails_only_its_own_case(tmp_path):
    src = _copy(tmp_path / "src")
    _add(src, "qwen3-0.6b", TRAFFIC, f"qwen3-0.6b.{TRAFFIC}", smoke=False)
    rc, got, out = _pytest(src, *TESTS, "-k", "not test_reference")
    assert got["FAILED"] == {"chipbench/tests/test_harness.py::"
                             f"test_every_cell_names_existing_files[qwen3-0.6b.{TRAFFIC}]"}, out
    assert rc == 1 and len(got["PASSED"]) >= 15, out
