"""The benchmark's harness: runs one cell once and prints its result line.

Everything that belongs to one configuration, traffic mix, cell or metric is
a file of its own, found by the name ``BENCHMARK.json`` gives it:

- ``chipbench/configs/<config>.json``: the sizes as run, the program's
  architecture id (``arch``) and overrides, the name of its plain
  reference in ``chipbench/references/``, and optionally (``costs``) the
  class that counts its operations and bytes, as ``<module>.<class>`` of
  ``chipbench`` (``flops.Dense`` where the key is absent);
- ``chipbench/traffic/<traffic>.json``: the traffic's parameters; its
  ``kind`` names the general generator and driver in ``chipbench/kinds/``;
- ``chipbench/limits/<cell>.json``: the limit of each number that decides
  ``correct`` in that cell;
- ``chipbench/metrics/<metric>.py``: the reader of a per-layer metric, a
  function ``read(run)`` that returns a number or None.

A run: load the files (before JAX starts), check that the device is a TPU
and that there are as many chips as the cell asks for, set up (counted in
``setup_s`` from the start of the process), run the measured window (traced
with ``--trace 1``), read the peak memory, free the program's state, run the
checks against the reference, and print one JSON line.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import time

from chipbench import trace

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WORK = os.path.join(HERE, ".work")  # fixed, ignored by git, emptied per run


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def metrics_of(bench: dict, cell: str, section: str) -> list[dict]:
    """The metrics of ``section`` that ``cell`` reports: those that list it,
    and those without a list; a per-layer metric without a list goes with
    the cells that report the end-to-end metric it moves."""
    e2e = {m["name"] for m in metrics_of(bench, cell, "end_to_end")} \
        if section == "per_layer" else set()
    out = []
    for m in bench[section]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif section == "end_to_end" or m["moves"] in e2e:
            out.append(m)
    return out


def _module_at(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@contextlib.contextmanager
def patched(module, name: str, make):
    """``module.name`` replaced by ``make(original)`` for the duration."""
    orig = getattr(module, name)
    setattr(module, name, make(orig))
    try:
        yield
    finally:
        setattr(module, name, orig)


class Cell:
    """Everything a run of one cell is made of, found by name."""

    def __init__(self, name: str, root: str = ROOT):
        bench = load_benchmark(root)
        here = os.path.join(root, "chipbench")
        by_name = {w["name"]: w for w in bench["workloads"]}
        if name not in by_name:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        self.name = name
        self.workload = by_name[name]
        entry = {c["name"]: c for c in bench["configs"]}[self.workload["config"]]
        self.config = load_json(os.path.join(root, entry["file"]))
        self.traffic = load_json(os.path.join(here, "traffic",
                                              self.workload["traffic"] + ".json"))
        self.limits = load_json(os.path.join(here, "limits", name + ".json"))
        self.end_to_end = metrics_of(bench, name, "end_to_end")
        self.per_layer = metrics_of(bench, name, "per_layer")
        self.kind = importlib.import_module(f"chipbench.kinds.{self.traffic['kind']}")
        self.reference = importlib.import_module(
            f"chipbench.references.{self.config['reference']}")
        self.readers = {
            m["name"]: _module_at(os.path.join(here, "metrics", m["name"] + ".py"),
                                  "chipbench_metric_" + m["name"].replace(".", "_")).read
            for m in self.per_layer
        }

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])


def program_config(config: dict):
    """The program's ModelConfig for a configuration file, checked against
    the sizes the file states."""
    from repro import configs

    cfg = configs.get(config["arch"]).replace(**config.get("program_overrides", {}))
    want = {
        "n_layers": config["num_hidden_layers"], "d_model": config["hidden_size"],
        "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"],
        "head_dim": config.get("head_dim") or config["hidden_size"] // config["num_attention_heads"],
        "d_ff": config["intermediate_size"], "vocab_size": config["vocab_size"],
        "rope_theta": float(config["rope_theta"]), "norm_eps": float(config["rms_norm_eps"]),
        "qk_norm": bool(config.get("qk_norm", False)),
        "tie_embeddings": bool(config["tie_word_embeddings"]),
        "padded_vocab": config["assumed"]["padded_vocab"],
    }
    got = {k: getattr(cfg, k) for k in want}
    differ = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
    if differ:
        raise ValueError(f"{config['name']}: the program runs {differ} (program, file)")
    return cfg


class Run:
    """What a cell's driver and the metric readers see of one run."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 peak: dict, work: str):
        self.cell, self.seed, self.seconds, self.trace = cell, seed, seconds, trace
        self.config, self.traffic = cell.config, cell.traffic
        self.peak, self.work = peak, work
        module, cls = cell.config.get("costs", "flops.Dense").rsplit(".", 1)
        costs = getattr(importlib.import_module(f"chipbench.{module}"), cls)
        self.model = costs.from_config(cell.config)
        self.data: dict = {}  # the driver's records of the window
        self.red = None  # the reduced trace, with --trace 1

    def span(self, name: str):
        """A benchmark span in the profiler's trace (traced runs only)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)


def _device(chips: int) -> dict:
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    log(f"device: platform {info['platform']}, kind {info['kind']!r}, "
        f"count {info['count']}")
    if info["platform"] != "tpu":
        raise SystemExit(f"no TPU: JAX found {info}")
    if info["count"] < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX found {info['count']}")
    return info


def _peak_memory(chips: int) -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:chips])


@contextlib.contextmanager
def _profiler(run: Run):
    """The profiler, on for the whole of a traced run's set-up and window
    (the set-up then compiles, and the profiler starts, under the same
    conditions as the window); the trace is read once it stops."""
    if not run.trace:
        yield
        return
    import jax

    tdir = os.path.join(run.work, "trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tdir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
    t0 = time.perf_counter()
    run.red = trace.read(tdir)
    log(f"trace read in {time.perf_counter() - t0:.3f} s: window "
        f"{run.red.window_s!r} s, busy {run.red.busy_s!r} s, "
        f"{len(run.red.ops)} device ops")
    shutil.rmtree(tdir, ignore_errors=True)


def one_program_per_call() -> None:
    """A Pallas kernel's serialised body keeps the innermost Python frames of
    the call that traced it, and JAX's persistent compilation cache keys on
    that body. The same train step traced from the set-up job, from the
    window, or through a benchmark span is then three programs, and the
    window's compiles inside the window. With no frames in locations it is
    one program, compiled in set-up and found again in the window."""
    import jax

    jax.config.update("jax_traceback_in_locations_limit", 0)


def judge(checks: dict) -> bool:
    """``checks``: {name: (value, limit)}; correct when every value is a
    number at or under its limit."""
    return all(isinstance(v, (int, float)) and math.isfinite(v) and v <= lim
               for v, lim in checks.values())


def main(argv: list[str], t_start: float) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = Cell(args.workload)  # every file is read before JAX starts
    from chipbench.peaks import peak

    device = _device(cell.chips)
    pk = peak(device["kind"])
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), device, pk, t_start)
    for k, c in result["checks"].items():
        log(f"check {k}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool, device: dict,
             pk: dict, t_start: float, work: str = WORK) -> dict:
    """Set up, measure, check; returns the result line as a dict. ``device``
    is what the caller found (the chip, once ``main`` has checked it)."""
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    one_program_per_call()
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    log(f"work dir {work}: {shutil.disk_usage(work).free} bytes free")
    device = dict(device)
    try:
        run = Run(cell, seed, seconds, traced, pk, work)
        driver = cell.kind.Driver(run)
        with _profiler(run):
            driver.setup()
            setup_s = time.perf_counter() - t_start
            log(f"set-up {setup_s!r} s")
            with run.span(trace.WINDOW):
                e2e = driver.window()
        device["memory_peak_bytes"] = _peak_memory(cell.chips)
        driver.release()
        t0 = time.perf_counter()
        checks = driver.check()
        log(f"checks took {time.perf_counter() - t0:.3f} s")
    finally:
        shutil.rmtree(work, ignore_errors=True)

    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    metrics = {}
    if run.trace:
        device["busy_s"] = run.red.busy_s
        device["window_s"] = run.red.window_s
        for name, read in cell.readers.items():
            value = read(run)
            if value is not None:
                metrics[name] = {"value": value, "unit": units[name]}
    else:
        for m in cell.end_to_end:
            value = setup_s if m["name"] == "setup_s" else e2e["metrics"][m["name"]]
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": judge(checks), "attempted": e2e["attempted"],
              "failed": e2e["failed"], "metrics": metrics, "device": device}
    if run.trace:
        result["breakdown"] = trace.breakdown(run.red)
    result["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    return result
