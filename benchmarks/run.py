"""Benchmark harness entry point.

One function per paper table/figure (benchmarks/paper_figs.py) plus the
SPMD-step microbenchmarks (benchmarks/spmd_step.py). Roofline terms for the
assigned architectures come from the dry-run artifacts and are reported by
benchmarks/roofline_report.py (reads launch/dryrun JSON output).

Prints ``name,us_per_call,derived`` CSV rows.

Usage:
  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run fig8 spmd  # substring filter
  PYTHONPATH=src python -m benchmarks.run kernel_vs_ref \
      --out BENCH_gossip_blend.json                  # + JSON records
  PYTHONPATH=src python -m benchmarks.run kernel_vs_ref_block_rows \
      --block-rows 32,64,128,256                     # block_rows sweep
  PYTHONPATH=src python -m benchmarks.run spmd kernel_vs_ref --tiny \
      # CI smoke: same dataflow + parity gates at ~1/256 state size

--out PATH writes every machine-readable record collected by the selected
benchmarks (benchmarks.common.record) plus the CSV rows as JSON — the perf
trajectory seed consumed by later PRs.
"""
from __future__ import annotations

import json
import platform
import sys
import traceback


def _parse_args(argv):
    filters, out, block_rows, tiny = [], None, None, False
    it = iter(argv)
    for a in it:
        if a == "--out":
            out = next(it, None)
            if out is None:
                raise SystemExit("--out requires a path")
        elif a.startswith("--out="):
            out = a.split("=", 1)[1]
        elif a == "--block-rows":
            block_rows = next(it, None)
            if block_rows is None:
                raise SystemExit("--block-rows requires a comma list")
        elif a.startswith("--block-rows="):
            block_rows = a.split("=", 1)[1]
        elif a == "--tiny":
            tiny = True
        elif not a.startswith("-"):
            filters.append(a)
    if block_rows is not None:
        block_rows = tuple(int(x) for x in block_rows.split(",") if x)
    return filters, out, block_rows, tiny


def main() -> None:
    filters, out_path, block_rows, tiny = _parse_args(sys.argv[1:])

    from repro.launch.cache import enable_compile_cache

    from . import paper_figs, roofline_report, spmd_step, stragglers
    enable_compile_cache()
    if tiny:
        # CI smoke lane: identical dataflow + derived/parity gates, state
        # sizes shrunk so every selected benchmark finishes in seconds —
        # execute-rot coverage, not a measurement (spmd_step._sz)
        spmd_step.TINY = True
    if block_rows:
        # kernel_vs_ref_block_rows sweep values (spmd_step.py)
        spmd_step.BLOCK_ROWS_SWEEP = block_rows
    groups = []
    groups += [(f.__name__, f) for f in paper_figs.ALL]
    groups += [(f.__name__, f) for f in spmd_step.ALL]
    groups += [(f.__name__, f) for f in stragglers.ALL]
    groups += [(f.__name__, f) for f in roofline_report.ALL]

    print("name,us_per_call,derived")
    failures = []
    for name, fn in groups:
        if filters and not any(f in name for f in filters):
            continue
        try:
            fn()
        except Exception as e:  # keep the harness going; report at end
            traceback.print_exc()
            failures.append((name, repr(e)))
    if out_path:
        from . import common
        import jax
        payload = {
            "backend": jax.default_backend(),
            "platform": platform.platform(),
            "records": common.records(),
            "rows": [{"name": n, "us_per_call": us, "derived": d}
                     for n, us, d in common.rows()],
        }
        with open(out_path, "w") as f:
            # allow_nan=False: fail fast rather than emit non-spec NaN
            # tokens into the machine-readable trajectory file
            json.dump(payload, f, indent=2, allow_nan=False)
        print(f"wrote {out_path} ({len(common.records())} records)",
              file=sys.stderr)

    if failures:
        for name, err in failures:
            print(f"FAILED,{name},{err}")
        raise SystemExit(1)


if __name__ == "__main__":
    main()
