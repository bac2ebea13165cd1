"""Reduction of a profiler trace to the numbers the per-layer metrics read.

An event is (name, start_ns, duration_ns).  The reduction works on plain
lists of events, one list of device operations per chip and one list of
host spans, so that it is tested on small synthetic traces;
:func:`load` reads them from the ``.xplane.pb`` that ``jax.profiler``
writes.
"""
from __future__ import annotations

import dataclasses
import pathlib

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
COLLECTIVE_PERMUTE = "collective-permute"
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Trace:
    devices: list[list[tuple]]   # per chip: (name, start_ns, dur_ns) ops
    host: list[tuple]            # host spans (name, start_ns, dur_ns)


def union(intervals) -> list[tuple[int, int]]:
    """Merged, sorted (start, end) intervals of (start, end) pairs."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def length(merged) -> int:
    return sum(e - s for s, e in merged)


def clip(merged, lo: int, hi: int) -> list[tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in merged
            if min(e, hi) > max(s, lo)]


def busy_ns(ops, lo: int, hi: int) -> int:
    """Time in [lo, hi) in which some operation ran."""
    return length(clip(union((s, s + d) for _, s, d in ops), lo, hi))


def gaps(ops, lo: int, hi: int) -> list[tuple[int, int]]:
    """The idle intervals of [lo, hi): no operation ran."""
    out, t = [], lo
    for s, e in clip(union((s, s + d) for _, s, d in ops), lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def time_by_name(ops, prefix: str) -> tuple[int, int]:
    """(calls, summed ns) of the operations whose name starts with
    ``prefix``: a kernel is found by the name its program gives it."""
    hits = [d for n, _, d in ops if n.startswith(prefix)]
    return len(hits), sum(hits)


def exposed_ns(ops, is_collective) -> int:
    """Time in which a collective ran and no other operation did."""
    coll = union((s, s + d) for n, s, d in ops if is_collective(n))
    other = union((s, s + d) for n, s, d in ops if not is_collective(n))
    covered, j = 0, 0
    for s, e in coll:
        while j < len(other) and other[j][1] <= s:
            j += 1
        k = j
        while k < len(other) and other[k][0] < e:
            covered += min(e, other[k][1]) - max(s, other[k][0])
            k += 1
    return length(coll) - covered


def is_permute(name: str) -> bool:
    return name.startswith(COLLECTIVE_PERMUTE)


def op_name(raw: str) -> str:
    """The HLO instruction's name, from an event name that may carry its
    whole text: ``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    return raw.lstrip("%").split(" ", 1)[0]


def leaves(ops) -> list[tuple]:
    """The operations that contain no other: a while loop or conditional
    spans the operations of its body on the same line."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    parent, stack = set(), []
    for i in order:
        s = ops[i][1]
        while stack and ops[stack[-1]][1] + ops[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            parent.add(stack[-1])
        stack.append(i)
    return [op for i, op in enumerate(ops) if i not in parent]


def top_ops(ops, n: int = 10) -> list[list]:
    """The ``n`` operation names that took most device time, in seconds."""
    tot = {}
    for name, _, d in ops:
        key = name.split(".")[0]
        tot[key] = tot.get(key, 0) + d
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in best]


def idle_gaps(ops, host, lo: int, hi: int, n: int = 10) -> list[list]:
    """The ``n`` longest idle gaps of the device, each named by the host
    span that overlaps it most ("host" where none does: the loop's own code
    between its calls), in seconds."""
    out = []
    for s, e in sorted(gaps(ops, lo, hi), key=lambda g: g[0] - g[1])[:n]:
        best, name = 0, "host"
        for hn, hs, hd in host:
            if hn == WINDOW_SPAN:
                continue
            ov = min(e, hs + hd) - max(s, hs)
            if ov > best:
                best, name = ov, hn
        out.append([name, (e - s) / 1e9])
    return out


def load(directory) -> Trace:
    """Device operations (the innermost ones, named by their HLO
    instruction) and the benchmark's host spans, from the newest
    ``.xplane.pb`` under ``directory``."""
    from jax.profiler import ProfileData

    path = max(pathlib.Path(directory).rglob("*.xplane.pb"),
               key=lambda p: p.stat().st_mtime)
    data = ProfileData.from_file(str(path))
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((op_name(e.name), int(e.start_ns),
                                int(e.duration_ns)) for e in line.events)
            devices[int(plane.name[len(DEVICE_PLANE):])] = leaves(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, int(e.start_ns), int(e.duration_ns))
                            for e in line.events
                            if e.name.startswith("bench."))
    return Trace(devices=[devices[k] for k in sorted(devices)], host=host)
