"""Readings that set the limits of a cell's correctness comparison.

  python3 bench/checks/control.py --workload NAME --seeds 1 2 3 \
      [--variants program control_bf16 wrong_peer ...]

For each seed, ``program`` drives the trainer through the cell's first
``check_steps`` steps exactly as a run's set-up does and compares them with
the reference, at the cell's own size on the chip.  Every other variant
puts the reference itself in the program's place: ``control_bf16``
computes its forward and backward passes in bfloat16, the precision below
the float32 the configuration states; the rest plant one fault each
(``half_batch``, ``no_exchange``, ``wrong_peer``; see
``bench.reference.asgd``).  Prints one JSON line per seed and variant with
the numbers the comparison reads, each step's loss gap, and where the gates
differ, the reference's margin there.  The control and every fault have to
fail at least one of the cell's limits.  The benchmark's own runs never run
this.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

# a state returned unchanged reads 1 on change_gap by construction: no run
VARIANTS = ("program", "control_bf16", "half_batch", "no_exchange",
            "wrong_peer")


def program_capture(cell: dict, seed: int, devices):
    """The trainer's first steps for ``seed``, captured as a run's set-up
    captures them: (settings, capture, leaf offsets)."""
    from bench import correct, harness
    from repro.launch.train import setup

    with contextlib.redirect_stdout(sys.stderr):
        run = setup(cell["argv"] + ["--seed", str(seed), "--steps", "0"],
                    devices=devices)
        harness.check_config(cell, run.cfg)
        st = harness.settings(cell, run)
        cap, _ = harness.first_steps(run, cell["check_steps"])
    offsets = correct.leaf_offsets(run.spec, harness.LANE)
    del run
    gc.collect()
    return st, cap, offsets


def diagnosis(got, ref) -> dict:
    """Each step's relative loss gap, and the gates that differ, with the
    reference's margin there."""
    import numpy as np

    steps, workers = np.nonzero(got.gates != ref.gates)
    return {"loss_gaps": (np.abs(got.losses - ref.losses)
                          / np.abs(ref.losses)).tolist(),
            "ref_open_gates": int(np.sum(ref.gates > 0)),
            "ref_open_margins": ref.margins[ref.gates > 0].tolist(),
            "gate_flips": [[int(t), int(w), float(ref.margins[t, w])]
                           for t, w in zip(steps, workers)]}


def readings(cell: dict, seed: int, variants, devices):
    """Yield (variant, numbers, diagnosis) for one seed."""
    import jax.numpy as jnp

    from bench import correct
    from bench.reference import asgd, model_of

    st, cap, offsets = program_capture(cell, seed, devices)
    model, steps = model_of(cell["cfg"]), cell["check_steps"]
    ref, w0 = asgd.run(model, cell["cfg"], st, seed, steps=steps)
    if "program" in variants:
        prog = correct.program_outputs(cap, w0, offsets, st.eps,
                                       st.block_rows)
        yield ("program", correct.compare(prog, ref),
               diagnosis(prog, ref))
        del prog
    del cap, w0
    for v in variants:
        if v == "program":
            continue
        kw = ({"dtype": jnp.bfloat16} if v == "control_bf16"
              else {"fault": v})
        got = asgd.run(model, cell["cfg"], st, seed, steps=steps, **kw)[0]
        yield v, correct.compare(got, ref), diagnosis(got, ref)


def main(argv=None) -> int:
    from bench import correct, harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--variants", nargs="+", default=list(VARIANTS),
                    choices=VARIANTS)
    a = ap.parse_args(argv)
    cell = harness.load_cell(a.workload)
    devices = harness.tpu_devices(cell["chips"])
    harness.enable_compile_cache()
    for seed in a.seeds:
        tseed = harness.trainer_seed(seed)
        for variant, numbers, diag in readings(cell, tseed, a.variants,
                                               devices):
            ok, _ = correct.judge(numbers, cell["limits"])
            print(json.dumps({"workload": a.workload, "seed": seed,
                              "variant": variant, "passes_limits": ok,
                              **numbers, **diag}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
