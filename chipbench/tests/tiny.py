"""A checkout-shaped directory holding a copy of the benchmark at smoke
widths, for running cells end to end on the CPU.

What a cell needs at smoke widths is found by name, as the harness finds a
cell's own files, under ``chipbench/tests/smoke/``:

- ``configs/<config>.json``: the keys of the configuration file that change
  (widths, depth, vocabulary and the program's overrides);
- ``traffic/<traffic>.json``: the keys of the traffic file that change;
- ``limits/<cell>.json``: the limits of ``correct`` at smoke widths, set by
  the same rule as the cells' own (between the largest sound reading and the
  smallest failing one) from smoke-size readings on the CPU: over a 512-row
  vocabulary the bf16 program's loss sits further from the reference's than
  at full width.

A cell added with its three smoke files is built and run like any other; a
cell without them is left out of ``make_root`` and fails only its own case
of ``test_every_cell_names_existing_files``.
"""
from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)

TRAIN = "qwen3-0.6b.train-ckpt"
SERVE = "granite-3-2b.serve-from-commit"


def load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def dump(path: str, obj) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def smoke_path(kind: str, name: str, src: str | None = None) -> str:
    """The smoke file of ``kind`` (``configs``, ``traffic`` or ``limits``)
    for ``name``, in the benchmark at ``src`` (the repository's own by
    default)."""
    return os.path.join(src or ROOT, "chipbench", "tests", "smoke", kind, name + ".json")


def smoke(kind: str, name: str, src: str | None = None) -> dict:
    return load(smoke_path(kind, name, src))


def has_smoke(workload: dict, src: str | None = None) -> bool:
    """Whether the cell ``workload`` (an entry of ``workloads``) has its
    three smoke files."""
    return all(os.path.exists(smoke_path(kind, workload[key], src)) for kind, key in
               (("configs", "config"), ("traffic", "traffic"), ("limits", "name")))


def make_root(dst: str, cells: list[str] | None = None, src: str | None = None) -> str:
    """A directory shaped like a checkout of the benchmark at ``src`` (the
    repository's own by default): its ``BENCHMARK.json`` as it is, and
    ``chipbench/`` with the configuration, traffic and limits of each cell in
    ``cells`` cut to smoke size (by default every cell with its smoke
    files); metric readers as they are."""
    src = src or ROOT
    bench = load(os.path.join(src, "BENCHMARK.json"))
    dump(os.path.join(dst, "BENCHMARK.json"), bench)
    files = {c["name"]: c["file"] for c in bench["configs"]}
    for w in bench["workloads"]:
        if (w["name"] not in cells) if cells is not None else not has_smoke(w, src):
            continue
        path = files[w["config"]]
        cfg = load(os.path.join(src, path))
        cfg.update(smoke("configs", w["config"], src))
        dump(os.path.join(dst, path), cfg)
        name = os.path.join("chipbench", "traffic", w["traffic"] + ".json")
        t = load(os.path.join(src, name))
        t.update(smoke("traffic", w["traffic"], src))
        dump(os.path.join(dst, name), t)
        name = os.path.join("chipbench", "limits", w["name"] + ".json")
        lim = load(os.path.join(src, name))
        lim.update(smoke("limits", w["name"], src))
        dump(os.path.join(dst, name), lim)
    shutil.copytree(os.path.join(src, "chipbench", "metrics"),
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
    device = {"platform": "cpu", "kind": "cpu", "count": c.chips}
    return harness.run_cell(c, seed, seconds, trace, device, PEAKS["TPU v5 lite"],
                            time.perf_counter(), work=work or os.path.join(root, "work"))
