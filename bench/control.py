"""Readings that the limits of ``correct`` are set from, in one process.

    python bench/control.py --workload <cell> --seeds <n,n,...> [--faults <k>]

For every seed: the program's readings from its set-up (the same
``Program`` and ``Readings`` that ``bench/run.py`` uses, no window) against
the plain reference's, which gives each compared number's lower reading.
On the first ``--faults`` seeds also the control, the reference computed
with float8 (e4m3, per-tensor scaled) matmuls where the configuration
states bfloat16, and each fault the cell can have, planted in the reference
put in the program's place (``half_batch``, ``no_exchange``,
``answer_altered``); their smallest readings are the upper ones. A step
that returns its state unchanged reads 1 on ``change_gap`` by
construction and needs no run. One JSON line per reading on stdout.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import check  # noqa: E402
from bench import run as harness  # noqa: E402

FAULTS = ("half_batch", "no_exchange", "answer_altered")


def readings(workload: str, seeds: list, faults: int, *,
             require_tpu: bool = True, overrides: dict | None = None):
    """Yields ``{"seed", "kind", "numbers"}`` for each reading."""
    c = harness.load_cell(workload, overrides)
    devices, _ = harness.device_info(c["cell"]["chips"], require_tpu)
    if require_tpu:
        harness.use_compile_cache()
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        prog = harness.Program(c, seed, devices)
        rd = harness.Readings(prog.names, prog.period, prog.initial_weights())
        while rd.step < rd.steps_needed:
            prog.period_run(rd)
        shapes, pool = prog.shapes, prog.pool
        del prog
        gc.collect()
        ref = harness.reference_readings(c, seed, shapes, pool, devices)
        yield {"seed": seed, "kind": "program",
               "numbers": check.compare(rd.out, ref),
               "seconds": time.perf_counter() - t0}
        if i >= faults:
            continue
        for kind, mm, fault in [("control", "fp8", None)] + [
                (f, "f32", f) for f in FAULTS]:
            t0 = time.perf_counter()
            got = harness.reference_readings(c, seed, shapes, pool, devices,
                                             mm=mm, fault=fault)
            yield {"seed": seed, "kind": kind,
                   "numbers": check.compare(got, ref),
                   "seconds": time.perf_counter() - t0}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated non-negative integers")
    ap.add_argument("--faults", type=int, default=3,
                    help="on how many of the seeds to read the control and "
                         "the faults")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    for r in readings(args.workload, seeds, args.faults):
        print(json.dumps(r), flush=True)


if __name__ == "__main__":
    main()
