#!/usr/bin/env python3
"""The readings the limits of a cell are set from, in one process.

  python3 bench/readings.py --workload <cell> --seeds 1 2 ... \\
      [--control-seeds 1 2 3] [--precisions int8 fp8] \\
      [--faults half_batch] [--out <file.jsonl>]

For each of ``--seeds``: the program's set-up steps (the timed path's own
executable, batches and sizes) against the float32 reference, as every run
compares them.  For each of ``--control-seeds``: the reference in each of
``--precisions`` in the program's place (``int8`` and ``fp8``, both
below the configured bfloat16) and each of ``--faults`` planted in the
program, against the same reference.  One JSON line per reading: the
seed, what was read, every number ``check.gaps`` gives, ``correct`` as
``check.judge`` decides it with the cell's limits, and per per-leaf
number its worst leaf and that leaf's reading.  The benchmark's own runs
do not run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))


def program_readings(cell, seed, devices, wrapper=None):
    from bench import run
    r = run.set_up(cell, seed, devices, wrapper)
    readings = r["readings"]
    del r
    gc.collect()
    return readings


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--faults", nargs="*", default=[])
    ap.add_argument("--precisions", nargs="*", default=["int8", "fp8"],
                    help="the reference in these precisions in the "
                         "program's place")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    from bench import check, faults, reference, run
    from bench.cells import resolve
    cell = resolve(args.workload)
    run.enable_cache()
    devices = run.tpu_devices(cell.chips)
    out = open(args.out, "a") if args.out else None

    def emit(seed, what, prog, secs, ref):
        leaves = check.per_leaf(prog, ref)
        numbers = check.gaps(prog, ref, leaves)
        rec = {"workload": cell.name, "seed": seed, "what": what, **numbers,
               "correct": check.judge(numbers, cell.limits)[0],
               "worst": {n: max((v, k) for k, v in by.items())[::-1]
                         for n, by in leaves.items()},
               "loss": prog["loss"], "ref_loss": ref["loss"],
               "secs": round(secs, 2)}
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    def timed(fn, *a, **kw):
        t = time.perf_counter()
        return fn(*a, **kw), time.perf_counter() - t

    for seed in sorted(set(args.seeds) | set(args.control_seeds)):
        ref, secs = timed(reference.readings, cell.config, cell.traffic, seed)
        print(f"reference seed {seed}: {secs:.1f} s", flush=True)
        if seed in args.seeds:
            emit(seed, "sound", *timed(program_readings, cell, seed,
                                       devices), ref)
        if seed in args.control_seeds:
            for precision in args.precisions:
                emit(seed, f"reference_{precision}",
                     *timed(reference.readings, cell.config, cell.traffic,
                            seed, precision=precision), ref)
            for name in args.faults:
                emit(seed, name, *timed(program_readings, cell, seed,
                                        devices, faults.FAULTS[name]), ref)
        gc.collect()
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
