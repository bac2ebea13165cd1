"""Device milliseconds per step under one of the train step's named scopes.

The device trace names each operation by its HLO instruction; the scope
path is in the ``op_name`` metadata of the compiled step
(bench/program_trace.py hlo_scopes).  That step is compiled again after
the window, from the trainer's own ``setup`` with the cell's argv, on the
cell's chips, and lowered with the arguments ``setup`` places, as the
trainer's first call is: the same program, so the same instruction names,
from the compile cache.  The time is the union of the leaf operations
under the scope in the traced window, averaged over the chips, per step
of the window; None where no operation runs under the scope (a program
without the scopes), or where the compiled step names less than
KNOWN_SHARE of the window's device time (another program than ran)."""
import contextlib
import sys

from bench import program_trace as pt
from bench import trace as tr

KNOWN_SHARE = 0.95


def compiled_step_text(ctx) -> str:
    import jax

    from bench.harness import trainer_seed
    from repro.launch.train import setup

    argv = ctx["cell"]["argv"] + ["--seed", str(trainer_seed(ctx["seed"])),
                                  "--steps", "0"]
    with contextlib.redirect_stdout(sys.stderr):
        run = setup(argv, devices=jax.devices()[:ctx["chips"]])
    s = run.state
    with jax.sharding.set_mesh(run.mesh):
        return run.step_fn.lower(
            s["params"], s["gossip"], s["opt"], run.next_wbatch(),
            jax.random.fold_in(run.key, 0), *run.live_args).compile().as_text()


def step_scopes(ctx) -> dict:
    """{instruction: op_name} of the cell's step, compiled once a run."""
    if "step_scopes" not in ctx:
        ctx["step_scopes"] = pt.hlo_scopes(compiled_step_text(ctx))
    return ctx["step_scopes"]


def device_ms(ctx, scope: str):
    t = ctx.get("trace")
    if t is None or not t.devices:
        return None
    lo, hi = ctx["trace_window"]
    scopes = step_scopes(ctx)
    ns = [pt.scope_ns(ops, scopes, scope, lo, hi) for ops in t.devices]
    if not any(ns):
        return None
    # the compiled step must name the window's operations: where it does
    # not, it is not the program that ran, and its scopes say nothing
    known = sum(tr.busy_ns([op for op in ops if op[0] in scopes], lo, hi)
                for ops in t.devices)
    if known < KNOWN_SHARE * sum(tr.busy_ns(ops, lo, hi)
                                 for ops in t.devices):
        return None
    return sum(ns) / len(ns) / 1e6 / ctx["steps"]
