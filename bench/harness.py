"""Drive one cell once: set up the trainer, check its first steps against
the reference, measure a window of its own loop, and read the metrics.

Set-up is the trainer's own ``setup`` from the cell's argv, then its own
``train`` for the cell's first ``check_steps`` steps (which compiles the
step and whose results are compared with the reference), then
``RATE_STEPS`` more to time a step.  The window is one more ``train``
call, with ``--steps`` raised by as many steps as fill the requested
seconds at that rate; it ends with ``block_until_ready`` on the state.  The benchmark adds no sync
inside it.  With ``trace`` the window is at most ``TRACE_STEPS`` steps long
and runs under the profiler, with a host span around each call the loop
makes into the trainer.
"""
from __future__ import annotations

import contextlib
import gc
import importlib
import json
import math
import os
import pathlib
import shutil
import sys
import tempfile
import time

import numpy as np

from bench.reference import model_of

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
RATE_STEPS = 2
TRACE_STEPS = 8
LANE = 512


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell needs."""


def process_age_s() -> float:
    """Seconds since this process started, from the kernel's clock."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


def load_json(path: pathlib.Path):
    with open(path) as f:
        return json.load(f)


def benchmark(root=ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def load_cell(name: str, root=ROOT) -> dict:
    """The cell's workload file, with its configuration under "cfg"."""
    cell = load_json(root / "bench" / "workloads" / f"{name}.json")
    cell["name"] = name
    cell["cfg"] = load_json(root / "bench" / "configs"
                            / f"{cell['config']}.json")
    return cell


def metric_reader(name: str):
    """The reader of metric ``name``: ``bench/metrics/<name>.py``'s
    ``read(ctx)``, which returns a number or None."""
    return importlib.import_module(f"bench.metrics.{name}").read


def metrics_of(cell: dict, trace: bool, root=ROOT) -> list[dict]:
    """The cell's end-to-end metrics, or with ``trace`` its per-layer ones:
    the entries of BENCHMARK.json whose "workloads" list names it, or that
    have none."""
    key = "per_layer" if trace else "end_to_end"
    return [m for m in benchmark(root)[key]
            if cell["name"] in m.get("workloads", [cell["name"]])]


def peaks(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r}; known: "
                       f"{sorted(table)}")
    return table[kind]


def tpu_devices(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache where the program keeps it
    (``$JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``), for
    every program the run compiles, however small."""
    import jax

    from repro.launch.cache import enable_compile_cache as program_cache

    where = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


class CompileCounter:
    """Counts compile requests and persistent-cache hits and misses."""

    def __init__(self):
        import jax

        self.counts = {}
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        self.counts[name] = self.counts.get(name, 0) + 1

    def _duration(self, name, _secs, **_):
        self._event(name)

    @property
    def compiles(self) -> int:
        return self.counts.get("/jax/core/compile/backend_compile_duration",
                               0)

    def snapshot(self) -> dict:
        return dict(self.counts)

    def close(self):
        import jax

        jax.monitoring.unregister_event_listener(self._event)
        jax.monitoring.unregister_event_duration_listener(self._duration)


def check_config(cell: dict, cfg) -> None:
    """The program must run the sizes the configuration file states: the
    family's reference module maps them to the program's names."""
    want = model_of(cell["cfg"]).program_sizes(cell["cfg"])
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        raise ValueError(f"the program runs {got}, the configuration file "
                         f"states {want}")


def trainer_seed(seed: int) -> int:
    """The trainer's seed: JAX keys and NumPy streams take 31 bits."""
    return seed % (2 ** 31)


def settings(cell: dict, run):
    from bench.reference.asgd import Settings

    a = run.args
    if run.spec.block_rows != cell["wire_block_rows"]:
        raise ValueError(f"the wire's tile is {run.spec.block_rows} rows, "
                         f"the cell states {cell['wire_block_rows']}")
    if not (a.pipelined and a.algo == "asgd" and a.inner == "sgd"
            and a.wire_format == "int8" and not a.elastic
            and a.lr_schedule == "none"):
        raise ValueError("the reference follows pipelined ASGD with plain "
                         "SGD steps and the int8 wire only")
    return Settings(workers=a.workers, batch=a.batch, seq=a.seq, eps=a.eps,
                    partial_blocks=a.partial_blocks, delay=a.delay,
                    block_rows=cell["wire_block_rows"], lane=LANE)


def _capture(run, steps: int):
    """Run the trainer's loop to ``steps`` through wrappers that keep host
    copies of the batches it is fed and the gates its step returns."""
    from repro.launch.train import train

    orig_next, orig_step = run.next_wbatch, run.step_fn
    tokens, gates = [], []

    def next_wbatch():
        batch = orig_next()
        tokens.append(np.asarray(batch["tokens"]))
        return batch

    def step_fn(*args):
        out = orig_step(*args)
        gates.append(out[3]["gate"])
        return out

    run.next_wbatch, run.step_fn = next_wbatch, step_fn
    try:
        run.args.steps = steps
        losses = train(run)
    finally:
        run.next_wbatch, run.step_fn = orig_next, orig_step
    return losses, tokens, [np.asarray(g) for g in gates]


def first_steps(run, steps: int) -> tuple[dict, float]:
    """Step the trainer through its first ``steps`` steps and keep what
    the comparison reads.  Returns (capture, seconds spent copying)."""
    import jax

    losses, tokens, gates = _capture(run, 1)
    t = time.perf_counter()
    w1 = np.asarray(run.state["params"])
    spent = time.perf_counter() - t
    more = _capture(run, steps)
    losses, tokens, gates = losses + more[0], tokens + more[1], gates + more[2]
    t = time.perf_counter()
    g = run.state["gossip"]
    buf, scales, part = g.buf, g.buf_scales, g.buf_idx
    if buf.ndim == 3:
        buf, scales, part = buf[None], scales[None], part[None]
    cap = {"w1": w1, "w_last": np.asarray(run.state["params"]),
           "fifo": np.asarray(buf), "fifo_scales": np.asarray(scales),
           "fifo_part": np.asarray(part),
           "group_rows": run.spec.group_row_ranges, "losses": losses,
           "tokens": np.stack(tokens), "gates": np.stack(gates)}
    jax.block_until_ready(run.state)
    return cap, spent + time.perf_counter() - t


def timed(run, steps: int, spans: dict | None):
    """``train`` to ``steps`` and wait for the state; with ``spans``, each
    call of the loop into the trainer runs under a host span of the
    profiler and its host seconds are kept there."""
    import jax

    from repro.launch.train import train

    orig = run.next_wbatch, run.step_fn
    if spans is not None:
        def wrap(name, fn):
            def call(*args):
                with jax.profiler.TraceAnnotation(f"bench.{name}"):
                    t = time.perf_counter()
                    out = fn(*args)
                    spans.setdefault(name, []).append(time.perf_counter() - t)
                return out
            return call
        run.next_wbatch = wrap("next_wbatch", run.next_wbatch)
        run.step_fn = wrap("step_fn", run.step_fn)
    run.args.steps = steps
    try:
        span = (contextlib.nullcontext() if spans is None
                else jax.profiler.TraceAnnotation("bench.window"))
        t = time.perf_counter()
        with span:
            losses = train(run)
            jax.block_until_ready(run.state)
        return losses, time.perf_counter() - t
    finally:
        run.next_wbatch, run.step_fn = orig


def check(cell: dict, st, tseed: int, cap: dict, offsets, log):
    """Run the reference over the cell's first steps and compare the
    program's capture with it: (correct, {name: {"value", "limit"}})."""
    import jax

    from bench import correct
    from bench.reference import asgd

    model = model_of(cell["cfg"])
    ref, w0 = asgd.run(model, cell["cfg"], st, tseed,
                       steps=cell["check_steps"])
    prog = correct.program_outputs(cap, w0, offsets, st.eps, st.block_rows)
    del w0
    numbers = correct.compare(prog, ref)
    names = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(jax.eval_shape(
                 lambda: model.init(cell["cfg"], jax.random.key(0))))[0]]
    log(correct.worst_leaves(prog, ref, names))
    log(f"reference losses {ref.losses.tolist()}")
    log(f"program losses {prog.losses.tolist()}")
    log(f"gates reference {ref.gates.tolist()} program "
        f"{prog.gates.tolist()}")
    log(f"reference gate margins {np.round(ref.margins, 6).tolist()}")
    return correct.judge(numbers, cell["limits"])


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, devices,
             *, born: float, log=print) -> dict:
    """One run of a cell on ``devices``.  ``born`` is the process's start
    on the ``time.perf_counter`` clock.  Returns the result line's dict."""
    import jax

    from bench import correct
    from repro.launch.train import setup

    compiles = CompileCounter()
    tseed = trainer_seed(seed)
    chips = len(devices)
    marks = {"imports": time.perf_counter() - born}
    with contextlib.redirect_stdout(sys.stderr):
        run = setup(cell["argv"] + ["--seed", str(tseed), "--steps", "0"],
                    devices=devices)
        marks["trainer setup"] = time.perf_counter() - born
        check_config(cell, run.cfg)
        st = settings(cell, run)
        cap, capture_s = first_steps(run, cell["check_steps"])
        marks["first steps"] = time.perf_counter() - born
        s0 = run.args.steps
        _, rate_s = timed(run, s0 + RATE_STEPS, None)
    step_s = rate_s / RATE_STEPS
    setup_s = time.perf_counter() - born - capture_s
    log(f"set-up {setup_s:.3f} s (copies for the check {capture_s:.3f} s "
        f"left out; seconds since start {marks}), step {step_s:.4f} s, "
        f"compile events {compiles.snapshot()}")

    n = max(1, round(seconds / step_s))
    if trace:
        n = min(n, TRACE_STEPS)
    spans = {} if trace else None
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    before = compiles.compiles
    with contextlib.redirect_stdout(sys.stderr):
        if trace:
            jax.profiler.start_trace(trace_dir)
        try:
            losses, window_s = timed(run, s0 + RATE_STEPS + n, spans)
        finally:
            if trace:
                jax.profiler.stop_trace()
    in_window = compiles.compiles - before
    compiles.close()
    if in_window:
        raise RuntimeError(f"{in_window} compiles inside the window")
    w, b, s = run.args.workers, run.args.batch, run.args.seq
    tokens = n * w * b * s
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devices)
    offsets = correct.leaf_offsets(run.spec, LANE)
    n_params = int(sum(run.spec.sizes))
    del run
    gc.collect()

    t = time.perf_counter()
    ok, checks = check(cell, st, tseed, cap, offsets, log)
    log(f"check took {time.perf_counter() - t:.3f} s")

    failed = int(sum(not math.isfinite(x) for x in losses))
    ctx = {"cell": cell, "chips": chips, "seed": seed, "steps": n,
           "tokens": tokens, "window_s": window_s, "setup_s": setup_s,
           "tokens_per_s": tokens / window_s / chips, "n_params": n_params,
           "workers": w, "seq": s, "settings": st,
           "peaks": peaks(devices[0].device_kind), "spans": spans}
    result = {"correct": ok and failed == 0, "attempted": n,
              "failed": failed, "metrics": {}}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": chips,
              "memory_peak_bytes": int(memory_peak)}
    if trace:
        from bench import trace as tr

        t = tr.load(trace_dir)
        shutil.rmtree(trace_dir)
        win = [h for h in t.host if h[0] == tr.WINDOW_SPAN][-1]
        lo, hi = win[1], win[1] + win[2]
        ctx["trace"], ctx["trace_window"] = t, (lo, hi)
        if t.devices:
            device["busy_s"] = float(np.mean(
                [tr.busy_ns(ops, lo, hi) for ops in t.devices]) / 1e9)
            device["window_s"] = (hi - lo) / 1e9
            result["breakdown"] = {
                "device_ops": tr.top_ops(t.devices[0]),
                "idle_gaps": tr.idle_gaps(t.devices[0], t.host, lo, hi)}
    for m in metrics_of(cell, trace):
        v = metric_reader(m["name"])(ctx)
        if v is not None:
            result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    result["device"] = device
    result["checks"] = checks
    for k, c in checks.items():
        log(f"check {k} {c['value']} limit {c['limit']}")
    return result
