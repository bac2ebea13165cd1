"""Bring-up smoke test of the ASGD trainer on a TPU.

Drives the trainer's own entry points (``repro.launch.train.setup`` and
``train``, which ``main`` composes) on smollm-135m at its published widths
— 30 layers, d_model 576, vocab 49152, ~134.5 M f32 parameters per worker,
nothing cut — with W = 4 workers, sequence 1024 and per-worker batch 1,
from random weights made from the seed.

  python chip_smoke.py             one chip (the default):
      1. the pipelined engine, int8 wire, delay 1, for 6 steps;
      2. the packed engine, int8 wire, delay 2 — the same schedule by
         construction (DESIGN.md §7); the two loss trajectories must agree;
      3. the step-0 loss against the same forward pass on the CPU backend
         at highest matmul precision;
      4. one resident-kernel round at real width against the jnp oracle
         (gossip_blend_w_resident_ref), int8 wire.
  python chip_smoke.py --chips 4   four-chip host: the pipelined int8 run
      with one worker per chip, against the same run on device 0 alone.

Every phase prints its numbers; any failure exits non-zero.  The last line
of standard output is one JSON object naming the device, printed only when
every phase passed.  With no TPU the script exits non-zero at once: it never
falls back to the CPU or to interpret mode.
"""
from __future__ import annotations

import argparse
import gc
import itertools
import json
import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

COMMON = ["--arch", "smollm-135m", "--workers", "4", "--batch", "1",
          "--seq", "1024", "--steps", "6", "--log-every", "1",
          "--partial-blocks", "4"]
PIPELINED = ["--pipelined", "--wire-format", "int8", "--delay", "1"]
PACKED = ["--packed-resident", "--wire-format", "int8", "--delay", "2"]

# per-step loss agreement between two runs of one schedule.  The two
# engines on one chip run the same float ops (bitwise equal on a v5e).
ENGINE_RTOL = 1e-4
# One chip vs four: XLA partitions and fuses the step differently, the
# step-0 losses differ by ~1e-6 relative, and at the default eps = 0.05
# (the loss rises after step 2) six steps amplify that to 5.1e-4 on a
# v5e; the bound keeps a 10x margin.
MESH_RTOL = 5e-3
# step-0 loss, TPU default matmul precision vs CPU at "highest"
CPU_REF_RTOL = 2e-3
# resident kernel vs jnp oracle, elementwise on w_next
KERNEL_ATOL = 1e-5


class SmokeError(RuntimeError):
    pass


def check(ok: bool, msg: str):
    if not ok:
        raise SmokeError(msg)


def log(msg: str):
    print(msg, flush=True)


def run_trainer(argv, devices=None, *, keep_first=False):
    """setup + train one run, with the step compiled up front and timed.

    Returns a dict: losses, compile and step seconds, the compiled step's
    HLO text and memory, the mesh, and (``keep_first``) worker 0's initial
    packed rows and the first batch on the host."""
    from repro.launch.train import setup, train

    run = setup(argv, devices=devices)
    first = run.next_wbatch()
    batches = itertools.chain([first], iter(run.next_wbatch, None))
    run.next_wbatch = lambda: next(batches)
    st = run.state
    args = (st["params"], st["gossip"], st["opt"], first,
            jax.random.fold_in(run.key, 0), *run.live_args)
    t0 = time.perf_counter()
    with jax.sharding.set_mesh(run.mesh):
        compiled = run.step_fn.lower(*args).compile()
    compile_s = time.perf_counter() - t0
    out = {"compile_s": compile_s, "hlo": compiled.as_text(),
           "memory": compiled.memory_analysis(), "mesh": run.mesh,
           "spec": run.spec, "cfg": run.cfg}
    if keep_first:
        out["rows0"] = np.asarray(st["params"][0])
        out["batch"] = jax.tree.map(np.asarray, first)
    del args, st

    step_s = []

    def timed_step(*a):
        # the executable compiled above: the jit call path would compile
        # the same program again
        t = time.perf_counter()
        res = jax.block_until_ready(compiled(*a))
        step_s.append(time.perf_counter() - t)
        return res

    run.step_fn = timed_step
    out["losses"] = train(run)
    out["step_s"] = step_s
    del run
    gc.collect()
    return out


def device_bytes(mem) -> int:
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)


def report(name: str, r: dict):
    m = r["memory"]
    steady = r["step_s"][1:] or r["step_s"]
    log(f"[{name}] mesh={dict(r['mesh'].shape)} compile_s={r['compile_s']} "
        f"first_step_s={r['step_s'][0]} "
        f"median_step_s={float(np.median(steady))}")
    log(f"[{name}] memory_analysis: argument_bytes="
        f"{m.argument_size_in_bytes} output_bytes={m.output_size_in_bytes} "
        f"temp_bytes={m.temp_size_in_bytes} alias_bytes="
        f"{m.alias_size_in_bytes} total_bytes={device_bytes(m)}")
    log(f"[{name}] losses={r['losses']}")
    check(all(np.isfinite(r["losses"])), f"{name}: non-finite loss")
    check("tpu_custom_call" in r["hlo"],
          f"{name}: the compiled step holds no Pallas TPU kernel")


def compare_losses(name: str, a, b, rtol: float):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    rel = float(np.max(np.abs(a - b) / np.abs(b)))
    log(f"[{name}] max_rel_diff={rel} tol={rtol}")
    check(a.shape == b.shape and rel <= rtol,
          f"{name}: loss trajectories disagree (max rel diff {rel} > {rtol})")


def cpu_reference_loss(r: dict) -> float:
    """Mean step-0 loss of the W workers, on the CPU backend at highest
    matmul precision, from the same initial rows and the same batch."""
    from repro.core.packing import unpack_rows
    from repro.models import model as M

    cpu = jax.devices("cpu")[0]
    cfg, spec = r["cfg"], r["spec"]
    with jax.default_device(cpu), jax.default_matmul_precision("highest"):
        rows = jax.device_put(r["rows0"], cpu)
        batch = jax.device_put(r["batch"], cpu)

        @jax.jit
        def loss(rows, b):
            return M.loss_fn(cfg, unpack_rows(rows, spec), b)

        losses = [float(loss(rows, jax.tree.map(lambda x: x[w], batch)))
                  for w in range(batch["tokens"].shape[0])]
    return float(np.mean(losses))


def kernel_round(n_workers: int, rows: int, block_rows: int = 64):
    """One resident blend round (int8 wire) at real width against the jnp
    oracle.  Workers 0..W/2-1 receive an external on the side of their
    step (gate opens), the others one behind it (gate closes)."""
    from repro.core.packing import quantize_rows
    from repro.kernels import LANE
    from repro.kernels.gossip_blend import gossip_blend_w_resident
    from repro.kernels.gossip_blend.ref import gossip_blend_w_resident_ref

    eps, shape = 0.05, (n_workers, rows, LANE)
    kw, kd = jax.random.split(jax.random.key(1))
    w = jax.random.normal(kw, shape, jnp.float32)
    dw = jax.random.normal(kd, shape, jnp.float32)
    side = jnp.where(jnp.arange(n_workers) < n_workers // 2, -0.5, 0.5)
    q, s = jax.jit(quantize_rows, static_argnums=1)(
        w + side[:, None, None] * dw, block_rows)
    row_range = jnp.asarray([rows // 4, rows // 2], jnp.int32)

    @jax.jit
    def compare(w, dw, q, s, row_range):
        args = (w, dw, q[:, None], row_range, eps)
        kw = {"ext_scales": s[:, None], "block_rows": block_rows}
        out_k, gates_k = gossip_blend_w_resident(*args, **kw)
        out_r, gates_r = gossip_blend_w_resident_ref(*args, **kw)
        return jnp.max(jnp.abs(out_k - out_r)), gates_k, gates_r

    err, gates_k, gates_r = compare(w, dw, q, s, row_range)
    err, gates_k, gates_r = float(err), np.asarray(gates_k), np.asarray(gates_r)
    log(f"[kernel_round] W={n_workers} rows={rows} block_rows={block_rows} "
        f"gates_kernel={gates_k[:, 0].tolist()} "
        f"gates_ref={gates_r[:, 0].tolist()} max_abs_diff={err} "
        f"tol={KERNEL_ATOL}")
    check(np.array_equal(gates_k, gates_r), "kernel_round: gates differ")
    check(gates_k.min() == 0.0 and gates_k.max() == 1.0,
          "kernel_round: expected both open and closed gates")
    check(err <= KERNEL_ATOL, f"kernel_round: max abs diff {err}")


def one_chip():
    from repro.kernels import resolve_interpret

    check(resolve_interpret(None) is False,
          "resolve_interpret(None) would interpret the kernels")
    log("resolve_interpret(None)=False")
    devices = jax.devices()[:1]
    pipe = run_trainer(COMMON + PIPELINED, devices, keep_first=True)
    n_params = sum(int(np.prod(s)) for s in pipe["spec"].shapes)
    log(f"[config] smollm-135m layers={pipe['cfg'].n_layers} "
        f"d_model={pipe['cfg'].d_model} vocab={pipe['cfg'].vocab} "
        f"params_per_worker={n_params} packed_rows={pipe['spec'].rows}")
    report("pipelined_int8_delay1", pipe)
    packed = run_trainer(COMMON + PACKED, devices)
    report("packed_int8_delay2", packed)
    compare_losses("pipelined_vs_packed", pipe["losses"], packed["losses"],
                   ENGINE_RTOL)

    ref = cpu_reference_loss(pipe)
    rel = abs(pipe["losses"][0] - ref) / abs(ref)
    log(f"[cpu_reference] step0_loss_tpu={pipe['losses'][0]} "
        f"step0_loss_cpu_highest={ref} rel_diff={rel} tol={CPU_REF_RTOL}")
    check(rel <= CPU_REF_RTOL, "step-0 loss disagrees with the CPU reference")
    rows = pipe["spec"].rows
    del pipe, packed
    gc.collect()
    kernel_round(4, rows)


def four_chips():
    devices = jax.devices()
    check(len(devices) == 4, f"--chips 4 needs 4 devices, found {len(devices)}")
    spread = run_trainer(COMMON + PIPELINED, devices)
    report("pipelined_int8_4chips", spread)
    check("collective-permute" in spread["hlo"],
          "4 chips: the compiled step holds no collective-permute")
    alone = run_trainer(COMMON + PIPELINED, devices[:1])
    report("pipelined_int8_device0", alone)
    compare_losses("4chips_vs_device0", spread["losses"], alone["losses"],
                   MESH_RTOL)
    ratio = device_bytes(spread["memory"]) / device_bytes(alone["memory"])
    log(f"[4chips_vs_device0] per_device_bytes_ratio={ratio}")
    check(ratio <= 0.3, f"per-device bytes ratio {ratio} is not ~1/4")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (jax found {dev.platform}); refusing to "
              "fall back", file=sys.stderr)
        return 2
    from repro.launch.cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    try:
        four_chips() if args.chips == 4 else one_chip()
    except SmokeError as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
