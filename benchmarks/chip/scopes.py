"""Attribute a traced step's device time to the program's named scopes.

The program opens ``jax.named_scope`` at its layer boundaries:
``forward``, ``accumulate``, ``grad_sync`` and ``optimizer`` in
``train/step.py``, ``bucket_<k>`` per bucket of the merge plan in
``core/comm.py`` and ``core/bucketer.py``, ``attention`` around the
attention core in ``models/layers.py``.  XLA keeps each instruction's scope
path in the compiled module's metadata (``op_name="jit(step_zero1)/while/
body/closed_call/transpose(jvp(forward))/..."``), fusions included.  JAX
wraps a scope opened inside a differentiated function in its
transformations: forward ops read ``jvp(forward)``, backward ops
``transpose(jvp(forward))``, and the ops ``jax.checkpoint`` recomputes in
the backward pass ``.../checkpoint/rematted_computation/...``.

A trace's ops carry the instruction's name only (``trace.op_name``), so the
map from name to path comes from the compiled program's text
(``compiled.as_text()``, :func:`op_names`), and each op is looked up in it.
The harness keeps no handle on the executable it traced, so
:func:`traced_op_names` builds the cell's step again and compiles it as
the harness does (from the persistent compile cache, which holds it since
set-up).  An op's device time is its self time (``trace.self_times``: a
``while`` less the ops of its body), over the ops wholly inside the traced
window, as ``trace.top_ops`` counts it.
"""

from __future__ import annotations

import functools
import json
import re
import sys
import time
import traceback

from benchmarks.chip import trace

CLASSES = ("grad_sync", "optimizer", "recompute", "backward", "forward",
           "other")

INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?\bmetadata=\{[^}]*?'
    r'\bop_name="((?:[^"\\]|\\.)*)"', re.M)
WRAPPED = re.compile(r"^[\w.\-]+\((.*)\)$")
BUCKET = re.compile(r"^bucket_(\d+)$")


def op_names(hlo_text: str) -> dict:
    """{instruction name: op_name} of every instruction of a compiled
    module's text that carries an ``op_name``."""
    return {m.group(1): m.group(2) for m in INSTRUCTION.finditer(hlo_text)}


@functools.lru_cache(maxsize=1 << 16)
def scope_names(path: str) -> frozenset:
    """The scope names on ``path``: each component, and each name a
    transformation wraps in it (``transpose(jvp(forward))`` gives itself,
    ``jvp(forward)`` and ``forward``)."""
    out = set()
    for c in path.split("/"):
        out.add(c)
        while m := WRAPPED.match(c):
            c = m.group(1)
            out.add(c)
    return frozenset(out)


@functools.lru_cache(maxsize=1 << 16)
def classify(path: str | None) -> str:
    """The class of an op whose scope path is ``path`` (None: not in the
    map); the first rule that matches wins."""
    if path is None:
        return "other"
    names = scope_names(path)
    if "grad_sync" in names:
        return "grad_sync"
    if "optimizer" in names:
        return "optimizer"
    if "rematted_computation" in names:
        return "recompute"
    if "accumulate" in names or any(c.startswith("transpose(")
                                    for c in path.split("/")):
        return "backward"
    if "forward" in names:
        return "forward"
    return "other"


def _timed(ops, lo: float, hi: float):
    """(op, self time) of the ops wholly inside [lo, hi]."""
    return [(o, t) for o, t in zip(ops, trace.self_times(ops))
            if o.start >= lo and o.end <= hi]


def scope_times(ops, names: dict, lo: float, hi: float) -> dict:
    """Self time (ns) of one device's ops in [lo, hi], by class."""
    out = dict.fromkeys(CLASSES, 0.0)
    for o, t in _timed(ops, lo, hi):
        out[classify(names.get(o.name))] += t
    return out


def scope_time(ops, names: dict, scope: str, lo: float, hi: float) -> float:
    """Self time (ns) in [lo, hi] of the ops with ``scope`` on their path,
    whatever their class."""
    return sum(t for o, t in _timed(ops, lo, hi)
               if scope in scope_names(names.get(o.name, "")))


def bucket_times(ops, names: dict, lo: float, hi: float) -> dict:
    """{k: self time (ns) in [lo, hi] of the ops under ``bucket_<k>``}."""
    out = {}
    for o, t in _timed(ops, lo, hi):
        for n in scope_names(names.get(o.name, "")):
            if m := BUCKET.match(n):
                k = int(m.group(1))
                out[k] = out.get(k, 0.0) + t
    return out


def other_ops(ops, names: dict, lo: float, hi: float, top: int = 5) -> list:
    """The ``top`` ops of class ``other`` with most self time in [lo, hi],
    as (the trace's text cut to 80 characters, ns)."""
    tot = {}
    for o, t in _timed(ops, lo, hi):
        if classify(names.get(o.name)) == "other":
            key = (o.text or o.name)[:80]
            tot[key] = tot.get(key, 0.0) + t
    return sorted(tot.items(), key=lambda kv: -kv[1])[:top]


# ---------------------------------------------------------------------------
# The traced program's op names.
# ---------------------------------------------------------------------------

def compile_text(cell, chips: int) -> str:
    """The compiled text of ``cell``'s step on the first ``chips``
    devices, built and compiled with the harness's own calls, on shapes
    alone (no weights, no batch on the device)."""
    import jax

    from benchmarks.chip import feed, harness

    b = harness.build(cell, jax.devices()[:chips])
    state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        jax.eval_shape(b.init_fn, jax.random.PRNGKey(0)), b.state_sh)
    batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=b.batch_sh)
             for k, v in feed.batch_at(cell.traffic, b.cfg.vocab_size, 0, 0,
                                       b.rows).items()}
    return harness.compile_step(b, state, batch).as_text()


_TRACED: dict = {}      # the last traced program's op names, by its cell


def traced_op_names(ctx) -> dict:
    """{instruction name: op_name} of the step ``ctx`` traced, made once
    per cell and process (every scope metric of the run reads it); logs
    the time it took and :func:`report`.  Empty where the step cannot be
    compiled again: the scope metrics then read None, and the traced run
    goes on."""
    key = json.dumps([ctx.cell.name, ctx.cell.config, ctx.cell.traffic,
                      ctx.chips], sort_keys=True)
    if key in _TRACED:
        return _TRACED[key]
    t = time.perf_counter()
    try:
        names = op_names(compile_text(ctx.cell, ctx.chips))
    except Exception:               # a profiling aid must not fail the run
        _log(f"scopes: the step did not compile again, so no op names:\n"
             f"{traceback.format_exc()}")
        names = {}
    _log(f"scopes: {len(names)} op names in "
         f"{time.perf_counter() - t:.3f} s (build, compile, print, parse)")
    _TRACED.clear()
    _TRACED[key] = names
    for line in report(ctx, names):
        _log(line)
    return names


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# What the metric files read.
# ---------------------------------------------------------------------------

def per_step_ms(ctx, ns_of) -> float:
    """``ns_of(ops)`` of each chip's ops in ms per traced step, the mean
    over the chips."""
    devs = sorted(ctx.trace.ops)
    return sum(ns_of(ctx.trace.ops[d]) for d in devs) / len(devs) \
        / ctx.steps / 1e6


def class_ms(ctx, cls: str) -> float | None:
    """Device self time of class ``cls`` in ms per step; None where no
    instruction of the traced program is of that class."""
    names = traced_op_names(ctx)
    if not any(classify(p) == cls for p in names.values()):
        return None
    return per_step_ms(ctx, lambda ops: scope_times(
        ops, names, ctx.lo, ctx.hi)[cls])


def scope_ms(ctx, scope: str) -> float | None:
    """Device self time under ``scope`` in ms per step; None where no
    instruction of the traced program is under it."""
    names = traced_op_names(ctx)
    if not any(scope in scope_names(p) for p in names.values()):
        return None
    return per_step_ms(ctx, lambda ops: scope_time(
        ops, names, scope, ctx.lo, ctx.hi))


def report(ctx, names: dict) -> list:
    """Lines for the traced run's log: each bucket's time, the ``other``
    time with its largest ops, the share of the time whose op has a name
    in ``names``, and the classes' sum against the device's busy time
    (``trace.busy``, the mean over the chips), all per step and chip."""
    lo, hi = ctx.lo, ctx.hi
    devs = sorted(ctx.trace.ops)
    if not names or not devs:
        return ["scopes: no op names or no device ops; no attribution"]
    per = {}
    for d in devs:
        for k, ns in bucket_times(ctx.trace.ops[d], names, lo, hi).items():
            per[k] = per.get(k, 0.0) + ns
    norm = len(devs) * ctx.steps * 1e6
    lines = [f"bucket {k}: {per[k] / norm!r} ms/step" for k in sorted(per)]
    cls = dict.fromkeys(CLASSES, 0.0)
    for d in devs:
        for c, ns in scope_times(ctx.trace.ops[d], names, lo, hi).items():
            cls[c] += ns
    lines.append(f"other: {cls['other'] / norm!r} ms/step; largest on "
                 f"chip {devs[0]}:")
    lines += [f"  {ns / 1e6 / ctx.steps!r} ms/step {text}" for text, ns in
              other_ops(ctx.trace.ops[devs[0]], names, lo, hi)]
    total = sum(cls.values())
    named = sum(t for d in devs for o, t in _timed(ctx.trace.ops[d], lo, hi)
                if o.name in names)
    busy = sum(trace.busy(ctx.trace.ops[d], lo, hi) for d in devs)
    lines.append(f"scopes: six classes sum to {total / norm!r} ms/step "
                 f"against busy {busy / norm!r} ms/step; "
                 f"{named / norm!r} ms/step of ops whose instruction has an "
                 f"op_name in the program's text")
    return lines
