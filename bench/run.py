"""Run one benchmark cell once and print its result line.

  python3 bench/run.py --workload NAME --seed N --seconds S --trace 0|1

NAME is a file ``bench/workloads/NAME.json``.  The run needs a TPU with as
many chips as the cell asks for; without one it exits non-zero and prints
no result.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (with
``--trace 1`` also ``breakdown``) and, last, ``checks``: each number the
correctness comparison read, beside its limit.  The same numbers close
standard error.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    from bench import harness

    born = time.perf_counter() - harness.process_age_s()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    cell = harness.load_cell(a.workload)
    try:
        devices = harness.tpu_devices(cell["chips"])
    except harness.NoChip as e:
        log(f"bench: {e}; refusing to run")
        return 2
    harness.peaks(devices[0].device_kind)
    harness.enable_compile_cache()
    result = harness.run_cell(cell, a.seed, a.seconds, bool(a.trace),
                              devices, born=born, log=log)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
