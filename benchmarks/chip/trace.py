"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

``load`` gives, for each device plane (``/device:TPU:<n>``):

* the ops of its ``XLA Ops`` line.  An event's name there is the HLO
  instruction's text (``%fusion.12 = bf16[...] fusion(...)``); the op's
  name is what precedes `` = ``.  A control-flow op (``while``) spans the
  ops of its body, so ops nest;
* the spans of its ``Async XLA Ops`` line: an asynchronous op from its
  ``*-start`` to its ``*-done``.

and the benchmark's host spans: the ``TraceAnnotation`` events it wrote
(``window``, ``data``, ...).  Device and host times share the profiler's
clock, in nanoseconds.

A collective (all-reduce, reduce-scatter, all-gather, all-to-all,
collective-permute) is known by its HLO opcode, the first word followed by
``(`` after the `` = `` of its text, whatever the instruction is called
(``%psum.45 = f32[] all-reduce(...)``); an op with no such text by its
name.  It counts from its start to its done.  Where the trace
has no asynchronous line, a ``*-start`` op is paired with the next
``*-done`` op of the same collective.

The interval arithmetic is plain Python on lists of (start, end) pairs, so
that it can be checked by hand.
"""

from __future__ import annotations

import dataclasses
import re

COLLECTIVE = re.compile(
    r"^(all-reduce|reduce-scatter|all-gather|all-to-all|collective-permute)"
    r"(-start|-done)?([.-]|$)")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:(\d+)$")


OPCODE = re.compile(r"(?:^|\s)([a-z][\w-]*)\(")


def op_name(text: str) -> str:
    return text.split(" = ", 1)[0].lstrip("%")


def collective_of(o) -> re.Match | None:
    """The match of ``COLLECTIVE`` on the op's opcode (kind, phase), or
    None where it is no collective."""
    _, eq, rhs = o.text.partition(" = ")
    if not eq:
        return COLLECTIVE.match(o.name)
    m = OPCODE.search(rhs)
    return COLLECTIVE.match(m.group(1)) if m else None


@dataclasses.dataclass
class Op:
    name: str
    start: float
    end: float
    text: str = ""


@dataclasses.dataclass
class Trace:
    ops: dict          # device id -> [Op] of the XLA Ops line, by start
    async_ops: dict    # device id -> [Op] of the Async XLA Ops line
    spans: list        # [Op]: the benchmark's host spans, by start

    def span(self, name: str) -> Op:
        """The one host span called ``name``."""
        found = [s for s in self.spans if s.name == name]
        if len(found) != 1:
            raise ValueError(f"{len(found)} host spans named {name!r}")
        return found[0]


def _ops(events) -> list:
    out = [Op(op_name(e.name), e.start_ns, e.start_ns + e.duration_ns, e.name)
           for e in events]
    out.sort(key=lambda o: (o.start, -o.end))
    return out


def from_profile(pd, span_names) -> Trace:
    """A :class:`Trace` from a ``jax.profiler.ProfileData``; host events
    whose name is in ``span_names`` become spans."""
    ops, async_ops, spans = {}, {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                ops[int(m.group(1))] = _ops(line.events)
            elif m and line.name == ASYNC_LINE:
                async_ops[int(m.group(1))] = _ops(line.events)
            elif not m:
                spans += [Op(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events if e.name in span_names]
    spans.sort(key=lambda o: o.start)
    return Trace(ops, async_ops, spans)


def load(path: str, span_names) -> Trace:
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(path), span_names)


# ---------------------------------------------------------------------------
# Intervals.
# ---------------------------------------------------------------------------

def clip(intervals, lo: float, hi: float) -> list:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def union(intervals) -> list:
    """Sorted, disjoint intervals covering the same points."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def length(intervals) -> float:
    return sum(b - a for a, b in union(intervals))


def minus(intervals, cover) -> list:
    """The parts of ``intervals`` that ``cover`` leaves uncovered."""
    out = []
    cover = union(cover)
    for a, b in union(intervals):
        for c, d in cover:
            if d <= a or c >= b:
                continue
            if c > a:
                out.append((a, c))
            a = max(a, d)
            if a >= b:
                break
        if a < b:
            out.append((a, b))
    return out


# ---------------------------------------------------------------------------
# Ops.
# ---------------------------------------------------------------------------

def self_times(ops) -> list:
    """Each op's time less that of the ops nested directly inside it, in
    the order of ``ops`` (sorted by start, longest first)."""
    own = [o.end - o.start for o in ops]
    stack = []
    for i, o in enumerate(ops):
        while stack and ops[stack[-1]].end <= o.start:
            stack.pop()
        if stack:
            own[stack[-1]] -= o.end - o.start
        stack.append(i)
    return own


def leaves(ops) -> list:
    """The ops with no op nested inside them."""
    own = self_times(ops)
    return [o for o, t in zip(ops, own) if t == o.end - o.start]


def collectives(ops, async_ops) -> list:
    """(start, end) of every collective of one device."""
    spans = [(o.start, o.end) for o in async_ops
             if (m := collective_of(o)) and m.group(2) != "-done"]
    pending = {}
    for o in ops:
        m = collective_of(o)
        if not m:
            continue
        kind, phase = m.group(1), m.group(2)
        if phase is None:
            spans.append((o.start, o.end))
        elif not async_ops and phase == "-start":
            pending.setdefault(kind, []).append(o.start)
        elif not async_ops and pending.get(kind):
            spans.append((pending[kind].pop(0), o.end))
    return spans


def busy(ops, lo: float, hi: float) -> float:
    """Time in [lo, hi] in which any op ran."""
    return length(clip([(o.start, o.end) for o in ops], lo, hi))


def collective_time(ops, async_ops, lo: float, hi: float):
    """(collective time, the part of it in which no other op ran), both in
    [lo, hi].  Other ops are the innermost ops that are not collectives."""
    coll = clip(collectives(ops, async_ops), lo, hi)
    other = [(o.start, o.end) for o in leaves(ops)
             if not collective_of(o)]
    return length(coll), length(minus(coll, clip(other, lo, hi)))


def idle_gaps(ops, spans, lo: float, hi: float, top: int = 10) -> list:
    """The longest stretches of [lo, hi] in which no op ran, each named by
    the innermost host span open at its middle (``other`` where none is)."""
    gaps = minus([(lo, hi)], clip([(o.start, o.end) for o in ops], lo, hi))
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (a + b) / 2
        names = [s.name for s in spans if s.start <= mid < s.end]
        out.append((names[-1] if names else "other", b - a))
    return out


def top_ops(ops, lo: float, hi: float, top: int | None = 10,
            width: int = 80) -> list:
    """The ops with most self time in [lo, hi], as (the first ``width``
    characters of the trace's name, ns); the ``top`` first (None: all)."""
    tot = {}
    for o, t in zip(ops, self_times(ops)):
        if o.start >= lo and o.end <= hi and t > 0:
            key = (o.text or o.name)[:width]
            tot[key] = tot.get(key, 0.0) + t
    return sorted(tot.items(), key=lambda kv: -kv[1])[:top]
