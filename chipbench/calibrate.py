"""Readings that set the limits of ``correct``: the program's, the control's
and a planted fault's, at the cell's own size, many seeds in one process.

    python3 chipbench/calibrate.py --workload <cell> --seeds 11,12,13 \\
        [--control] [--fault half_batch]

For each seed it prints one JSON line: ``{"seed", "what", "readings",
"correct"}``, ``what`` being ``program``, ``control`` or the fault's name,
and ``correct`` what ``harness.judge`` makes of the readings against the
cell's limits (``chipbench/limits/<cell>.json``). Training cells
run the set-up job only (the numbers compared come from its steps); serving
cells serve one whole cycle of the traffic, which holds its longest
requests, and read as many requests as a run does. The control is the
reference computed in float8 in the program's place. Benchmark runs never
run this.
"""
import argparse
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# the TPU runtime would otherwise keep its logs under a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def readings(cell, seed: int, what: str, control: bool, pk: dict,
             work: str) -> list[dict]:
    """[(what, readings)] for one seed: the program's (under a planted fault
    when ``what`` names one) and, with ``control``, the control's."""
    import contextlib

    from chipbench import faults, harness

    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    run = harness.Run(cell, seed, 0.0, False, pk, work)
    driver = cell.kind.Driver(run)
    out = []
    patch = faults.FAULTS[what]() if what in faults.FAULTS else contextlib.nullcontext()
    try:
        with patch:
            driver.setup()
            if cell.traffic["kind"] == "serve_closed":
                driver.window()  # --seconds 0: one whole cycle
                driver.release()
                r = driver.readings(control=control)
                out.append((what, {"token_gap": r["token_gap"]}))
                if control:
                    out.append(("control", {"token_gap": r["control_gap"]}))
            else:
                numbers = lambda r: {k: v for k, v in r.items()  # noqa: E731
                                     if k not in ("ref_losses", "leaves_compared")}
                out.append((what, numbers(driver.readings())))
                if control:
                    out.append(("control", numbers(driver.control())))
    finally:
        shutil.rmtree(work, ignore_errors=True)
    lim = cell.limits
    return [{"seed": seed, "what": w, "readings": r,
             "correct": harness.judge({k: (v, lim[k]) for k, v in r.items() if k in lim})}
            for w, r in out]


def main(argv=None) -> int:
    from chipbench import faults, harness
    from chipbench.peaks import peak

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", default="program", choices=["program", *faults.FAULTS])
    args = ap.parse_args(argv)
    cell = harness.Cell(args.workload)
    device = harness._device(cell.chips)
    pk = peak(device["kind"])
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    harness.one_program_per_call()
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        for line in readings(cell, seed, args.fault, args.control, pk, harness.WORK):
            line["seconds"] = time.perf_counter() - t0
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
