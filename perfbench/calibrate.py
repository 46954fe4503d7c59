"""Readings from which the limits of ``correct`` are set.

  python3 perfbench/calibrate.py --workload smollm.train --seeds 1,2,3 \
      [--seconds 0] [--fault train_half_batch] [--out readings.jsonl]

For each seed, in one process: set-up of the cell as a run makes it, a
window of ``--seconds`` (0 for training, whose numbers come from set-up),
then the cell's compared numbers for the program and for the control (the
reference in the program's place, one precision below the configuration's),
or, with ``--fault``, for the program with that fault planted. One JSON line
per seed on standard output and in ``--out``. The benchmark's own runs never
do this.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--fault", default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from run import configure_jax
    configure_jax()

    from perfbench.lib import harness
    from perfbench.tests import cellfiles
    bench = harness.load_benchmark()
    cell, _, config, traffic = harness.find_cell(bench, args.workload)
    device = harness.device_info(cell["chips"])
    kind = harness.load_kind(traffic["kind"])
    out = open(args.out, "a") if args.out else None
    try:
        for seed in [int(s) for s in args.seeds.split(",")]:
            t0 = time.perf_counter()
            planted = (cellfiles.fault(bench, args.workload, args.fault)()
                       if args.fault else contextlib.nullcontext())
            driver = kind.Cell(config, traffic, seed)
            try:
                with planted:
                    driver.setup()
                    if args.seconds > 0:
                        driver.window(args.seconds, traced=False)
                driver.release()
                line = {"workload": args.workload, "seed": seed,
                        "fault": args.fault, "device": device,
                        **driver.calibration(control=args.fault is None),
                        "seconds": time.perf_counter() - t0}
            finally:
                driver.close()
            print(json.dumps(line), flush=True)
            if out:
                out.write(json.dumps(line) + "\n")
                out.flush()
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
