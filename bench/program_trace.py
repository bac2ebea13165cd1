"""Reduction of the program's own spans and scopes in a profiler trace.

The trainer records host spans named ``train.*`` (``launch/train.py``) and
runs its step's forward/backward and gossip round under the named scopes
``step.fwd_bwd`` and ``step.gossip`` (``launch/steps.py``), which reach
each device operation as the ``op_name`` metadata of its HLO instruction.
A span is (name, start_ns, duration_ns) as in :mod:`bench.trace`; the
functions work on plain lists of them, so that they are tested on small
synthetic traces, and :func:`load` reads them from an ``.xplane.pb``.
"""
from __future__ import annotations

import dataclasses
import pathlib
import re

from bench import trace as tr

PREFIX = "train."
STEP_SPAN = "train.step"
# a line of HLO text: an instruction, or the header of a computation
_HLO_INST = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_HLO_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPERANDS = re.compile(r"[\w\-]\((%[^)]*)\)")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
# computations an instruction runs as a loop or a branch
_RUNS = re.compile(r"\b(?:body|condition|true_computation|false_computation)"
                   r"=%?([\w.\-]+)|branch_computations=\{([^}]*)\}")
_NAME = re.compile(r"%?([\w.\-]+)")


@dataclasses.dataclass
class ProgramTrace:
    spans: list[tuple]   # the program's train.* host spans
    host: list[tuple]    # the benchmark's bench.* host spans


def load(directory) -> ProgramTrace:
    """The host spans of the newest ``.xplane.pb`` under ``directory``."""
    from jax.profiler import ProfileData

    path = max(pathlib.Path(directory).rglob("*.xplane.pb"),
               key=lambda p: p.stat().st_mtime)
    data = ProfileData.from_file(str(path))
    spans, host = [], []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                ev = (e.name, int(e.start_ns), int(e.duration_ns))
                if e.name.startswith(PREFIX):
                    spans.append(ev)
                elif e.name.startswith("bench."):
                    host.append(ev)
    return ProgramTrace(spans=spans, host=host)


def steps_in(spans, lo: int, hi: int) -> list[tuple]:
    """The ``train.step`` spans that start in [lo, hi), in order."""
    return sorted((s for s in spans if s[0] == STEP_SPAN and lo <= s[1] < hi),
                  key=lambda s: s[1])


def children(spans, step) -> list[tuple]:
    """The program's spans other than steps that start inside ``step``."""
    s0, e0 = step[1], step[1] + step[2]
    return [s for s in spans if s[0] != STEP_SPAN and s0 <= s[1] < e0]


def per_step(spans, lo: int, hi: int) -> dict:
    """{name: (count, summed ns)} of the spans inside the steps that start
    in [lo, hi), with the steps themselves under ``train.step``."""
    out = {}
    for step in steps_in(spans, lo, hi):
        for name, _, d in [step] + children(spans, step):
            n, ns = out.get(name, (0, 0))
            out[name] = (n + 1, ns + d)
    return out


def self_ns(step, kids) -> int:
    """The step's duration less the part of it its child spans cover."""
    s0, e0 = step[1], step[1] + step[2]
    return step[2] - tr.length(tr.clip(
        tr.union((s, s + d) for _, s, d in kids), s0, e0))


def _innermost(spans, s: int, e: int):
    """Of the spans that overlap [s, e) and contain no other that does, the
    one that overlaps it most; None where no span overlaps it."""
    hit = [h for h in spans if min(e, h[1] + h[2]) > max(s, h[1])]
    inner = [h for h in hit
             if not any(h[1] <= o[1] and o[1] + o[2] <= h[1] + h[2]
                        and (o[1], o[2]) != (h[1], h[2]) for o in hit)]
    if not inner:
        return None
    return max(inner, key=lambda h: min(e, h[1] + h[2]) - max(s, h[1]))


def idle_gaps(ops, spans, host, lo: int, hi: int, n: int = 10) -> list:
    """The ``n`` longest idle gaps of the device, in seconds, each named by
    the innermost program span that overlaps it most; where none does, by
    the benchmark's span as :func:`bench.trace.idle_gaps` names it."""
    out = []
    for s, e in sorted(tr.gaps(ops, lo, hi), key=lambda g: g[0] - g[1])[:n]:
        inner = _innermost(spans, s, e)
        name = (inner[0] if inner
                else tr.idle_gaps([], host, s, e, n=1)[0][0])
        out.append([name, (e - s) / 1e9])
    return out


def hlo_scopes(text: str) -> dict:
    """{HLO instruction name: op_name path} of a compiled module's text.

    The compiler's own instructions (copies, reshapes, the loops it builds
    to move a large array) carry no metadata.  Such an instruction takes,
    in this order: the op_name nearest the root of the fusion computation
    it calls; that of its first operand that has one; that of the loop or
    branch that runs its computation."""
    comps, inner, comp, entry = {}, {}, None, None
    for line in text.splitlines():
        m = _HLO_INST.match(line)
        if m is None:
            if h := _HLO_COMP.match(line):
                comp = h.group(1)
                entry = comp if line.startswith("ENTRY") else entry
            continue
        rhs = line[m.end():]
        op = _OP_NAME.search(rhs)
        if op:
            inner[comp] = op.group(1)
        args = _OPERANDS.search(rhs)
        runs = [n for a, b in _RUNS.findall(rhs)
                for n in ([a] if a else _NAME.findall(b))]
        calls = _CALLS.search(rhs)
        comps.setdefault(comp, []).append(
            (m.group(1), op and op.group(1), calls and calls.group(1),
             _NAME.findall(args.group(1)) if args else [], runs))
    out = {}

    def resolve(comp, outer):
        for name, op, calls, args, runs in comps.get(comp, []):
            path = (op or inner.get(calls)
                    or next((out[a] for a in args if a in out), outer))
            if path:
                out[name] = path
            for c in runs:
                resolve(c, path)

    resolve(entry, None)
    return out


def in_scope(path: str, scope: str) -> bool:
    """Whether the op_name ``path`` runs under the named ``scope``: a
    component of the path, or the argument of a transformation in one
    (``transpose(jvp(step.fwd_bwd))`` is a backward op of that scope)."""
    return scope in re.split(r"[/()]", path)


def scope_ns(ops, scopes: dict, scope: str, lo: int, hi: int) -> int:
    """Device time in [lo, hi) in which an operation under ``scope`` ran;
    an operation is found in ``scopes`` by its instruction name."""
    return tr.busy_ns([op for op in ops
                       if in_scope(scopes.get(op[0], ""), scope)], lo, hi)
