#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 benchmark/calibrate.py --workload <name> --seeds 1,2,3

For each seed, in one process: generate the cell's cohort at its own size,
run one analysis through the same entry the window drives, and print one
JSON line with

- ``program``: the numbers run.py compares (``g_err``, ``coord_err``) for
  that analysis against the plain reference, the lower readings;
- ``control``: the same numbers for the reference computed a precision
  lower (``benchmark/reference.py``: G in bfloat16, and coordinates from
  G and the centred matrix held in bfloat16), the upper readings.

The reference is the one run.py compares with: G tiled over the cell's
chips, and above ``reference.DENSE_LIMIT`` samples the iterative top-k.

Benchmark runs do not run this.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from benchmark import reference, run  # noqa: E402
from benchmark.cohortlib import write_light_mirror  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    cell = run.resolve_cell(run.ROOT, args.workload)
    devices = run.require_devices(cell.chips)
    if devices is None:
        return 2
    devices = devices[:cell.chips]
    run.use_compile_cache(run.ROOT)
    import numpy as np

    cohort_cfg = cell.config["cohort"]
    generator = run.load_module(run.ROOT, "cohorts", cohort_cfg["generator"])
    k = int(cell.config["num_pc"])
    for seed in (int(s) for s in args.seeds.split(",")):
        cohort = generator.generate(cohort_cfg, seed)
        work = tempfile.mkdtemp(prefix="calibrate-")
        try:
            write_light_mirror(cohort, os.path.join(work, "c"))
            g, coords, rows = run.analysis(
                os.path.join(work, "c"), cohort.references(),
                list(cell.traffic["flags"]))
            g = np.asarray(g)
        finally:
            shutil.rmtree(work, ignore_errors=True)
        g_ref, c_ref = run.reference_answers(cohort, k, devices)
        program = run.compare(cohort, [(g, coords, rows)], k,
                              cell.config["limits"], (g_ref, c_ref))
        del g
        control = {
            "g_err": reference.g_err(reference.control_gramian(g_ref),
                                     g_ref),
            "coord_err": reference.coord_err(
                reference.control_coordinates(g_ref, k)[:, :2],
                c_ref[:, :2]),
        }
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "program": {n: program[n]["value"]
                        for n in ("g_err", "coord_err")},
            "control": control,
        }), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
