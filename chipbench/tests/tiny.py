"""A checkout-shaped directory holding a copy of the benchmark at smoke
widths, for running cells end to end on the CPU."""
from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)

TRAIN = "qwen3-0.6b.train-ckpt"
SERVE = "granite-3-2b.serve-from-commit"

SMOKE = {
    "qwen3-0.6b": {
        "program_overrides": {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
                              "d_head": 16, "d_ff": 128, "vocab_size": 512,
                              "norm_eps": 1e-06},
        "num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
        "vocab_size": 512, "assumed": {"padded_vocab": 512},
    },
    "granite-3-2b": {
        "program_overrides": {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
                              "d_head": 16, "d_ff": 128, "vocab_size": 500},
        "num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
        "vocab_size": 500, "assumed": {"padded_vocab": 512},
    },
}

# The limits of ``correct`` at smoke widths, set by the same rule as the
# cells' own (between the largest sound reading and the smallest failing
# one) from smoke-size readings on the CPU: over a 512-row vocabulary the
# bf16 program's loss sits further from the reference's than at full width.
LIMITS = {
    TRAIN: {"loss_gap": 1.5e-4, "grad_gap": 0.02, "update_gap": 0.06},
    SERVE: {"token_gap": 0.25},
}

TRAFFIC = {
    "train-ckpt": {"batch": 2, "seq_len": 64, "setup_steps": 3, "ckpt_every": 6},
    "serve-from-commit": {"batch": 2, "cycle": {"64": 2, "128": 1}, "gen_tokens": 8,
                          "check_requests": 2},
}


def load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def dump(path: str, obj) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def make_root(dst: str, limits: dict | None = None) -> str:
    """A directory shaped like a checkout: ``BENCHMARK.json`` and
    ``chipbench/`` with the configurations, traffic and limits cut to smoke
    size (``limits`` replaces ``LIMITS``); metric readers as they are."""
    bench = load(os.path.join(ROOT, "BENCHMARK.json"))
    dump(os.path.join(dst, "BENCHMARK.json"), bench)
    for c in bench["configs"]:
        cfg = load(os.path.join(ROOT, c["file"]))
        cfg.update(SMOKE[c["name"]])
        dump(os.path.join(dst, c["file"]), cfg)
    for w in bench["workloads"]:
        t = load(os.path.join(BENCH_DIR, "traffic", w["traffic"] + ".json"))
        t.update(TRAFFIC[w["traffic"]])
        dump(os.path.join(dst, "chipbench", "traffic", w["traffic"] + ".json"), t)
        lim = load(os.path.join(BENCH_DIR, "limits", w["name"] + ".json"))
        lim.update(LIMITS[w["name"]] if limits is None else limits.get(w["name"], {}))
        dump(os.path.join(dst, "chipbench", "limits", w["name"] + ".json"), lim)
    shutil.copytree(os.path.join(BENCH_DIR, "metrics"),
                    os.path.join(dst, "chipbench", "metrics"))
    return dst


def run(root: str, cell: str, seed: int = 7, seconds: float = 0.0,
        trace: bool = False, work: str | None = None) -> dict:
    """One run of ``cell`` from ``root`` on the CPU, past the look for a
    chip, costed against the v5e's peaks."""
    import time

    from chipbench import harness
    from chipbench.peaks import PEAKS

    c = harness.Cell(cell, root=root)
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    return harness.run_cell(c, seed, seconds, trace, device, PEAKS["TPU v5 lite"],
                            time.perf_counter(), work=work or os.path.join(root, "work"))
