"""The reduction from a profiler trace to the per-layer metrics.

A trace is built by hand (as a text proto of the profiler's XSpace) in the
shape the TPU writes, with times chosen so that every number can be worked
out on paper.  Device 0, ns:

  XLA Ops        while.1 [0, 200) holding fusion.1 [0, 60),
                 all-gather-start.1 [60, 65), fusion.2 [65, 120),
                 all-gather-done.1 [150, 160), fusion.3 [160, 190);
                 then all-reduce.3 [220, 260), fusion.4 [300, 350)
  Async XLA Ops  all-gather-start.1 [60, 160), copy-start.7 [0, 300)

device 1: fusion.9 [0, 400); host spans: window [0, 400), readback
[250, 290), data [340, 390).

Device 0 is busy on [0,200) [220,260) [300,350): 290 of 400 ns, so idle
27.5%.  Its collectives are the all-gather from start to done [60,160) and
the all-reduce [220,260): 140 ns.  The innermost other ops (the fusions;
not the while, which holds them) cover [65,120) of the first, so
[60,65) [120,160) and all of the all-reduce are exposed: 85 ns.  Its idle
gaps are [350,400) in ``data``, [260,300) in ``readback`` and [200,220)
in ``window``.  Self times: fusion.1 60, fusion.2 55, fusion.4 50, the
while 200 - 160 = 40.
"""

import pytest
from jax.profiler import ProfileData

from benchmarks.chip import harness, manifest, trace
from conftest import ROOT


def _plane(pid, name, lines):
    names = sorted({n for _, events in lines for n, _, _ in events})
    meta = "\n".join(
        f'  event_metadata {{ key: {i + 1} value {{ id: {i + 1} '
        f'name: "{n}" }} }}' for i, n in enumerate(names))
    body = ""
    for lid, (line, events) in enumerate(lines):
        evs = "\n".join(
            f"    events {{ metadata_id: {names.index(n) + 1} "
            f"offset_ps: {a * 1000} duration_ps: {(b - a) * 1000} }}"
            for n, a, b in events)
        body += (f'  lines {{\n    id: {lid + 1}\n    name: "{line}"\n'
                 f'    timestamp_ns: 0\n{evs}\n  }}\n')
    return f'planes {{\n  id: {pid}\n  name: "{name}"\n{body}{meta}\n}}\n'


OPS0 = [("%while.1 = (s32[]) while(s32[] %p)", 0, 200),
        ("%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %a)", 0, 60),
        ("%all-gather-start.1 = bf16[32]{0} all-gather-start(%b)", 60, 65),
        ("fusion.2", 65, 120), ("all-gather-done.1", 150, 160),
        ("fusion.3", 160, 190), ("all-reduce.3", 220, 260),
        ("fusion.4", 300, 350)]
ASYNC0 = [("%all-gather-start.1 = bf16[32]{0} all-gather-start(%b)", 60, 160),
          ("copy-start.7", 0, 300)]
HOST = [("window", 0, 400), ("readback", 250, 290), ("data", 340, 390),
        ("unrelated", 0, 50)]


def _trace(*planes):
    return trace.from_profile(ProfileData.from_text_proto("".join(planes)),
                              harness.SPANS)


@pytest.fixture
def hand():
    return _trace(
        _plane(1, "/device:TPU:0", [("XLA Ops", OPS0),
                                    ("Async XLA Ops", ASYNC0)]),
        _plane(2, "/device:TPU:1", [("XLA Ops", [("fusion.9", 0, 400)])]),
        _plane(3, "/host:CPU", [("python", HOST)]))


def test_reduction(hand):
    assert sorted(hand.ops) == [0, 1]
    assert [s.name for s in hand.spans] == ["window", "readback", "data"]
    w = hand.span("window")
    assert (w.start, w.end) == (0, 400)
    ops, aops = hand.ops[0], hand.async_ops[0]
    assert ops[0].name == "while.1" and ops[1].name == "fusion.1"
    assert trace.busy(ops, 0, 400) == 290
    assert trace.collective_time(ops, aops, 0, 400) == (140, 85)
    assert trace.collective_time(hand.ops[1], [], 0, 400) == (0, 0)
    assert trace.idle_gaps(ops, hand.spans, 0, 400) == [
        ("data", 50), ("readback", 40), ("window", 20)]
    top = trace.top_ops(ops, 0, 400, top=4, width=9)
    assert top == [("%fusion.1", 60), ("fusion.2", 55), ("fusion.4", 50),
                   ("%while.1 ", 40)]


def test_start_done_without_async_line():
    ops = [trace.Op("all-gather-start.1", 0, 5), trace.Op("fusion.1", 5, 20),
           trace.Op("all-gather-done.1", 30, 40)]
    # [0, 40) in flight, fusion.1 hides [5, 20)
    assert trace.collective_time(ops, [], 0, 100) == (40, 25)


CELL = "qwen2-1.5b-f32.train4k-fill"


def _ctx(tz, steps=2):
    cell = manifest.load_cell(CELL)
    w = tz.span("window") if tz else trace.Op("window", 0, 0)
    return harness.Context(cell, tz, w.start, w.end, [], steps, 1.0,
                           4096, 3.0e9, 4, {"bf16_flops_per_s": 197e12})


@pytest.mark.parametrize("name,value", [
    ("device_idle_share", 27.5),            # the worse of 27.5% and 0%
])
def test_device_metrics(hand, name, value):
    assert manifest.metric_reader(name)(_ctx(hand)) == pytest.approx(value)


def test_no_collectives_reads_nothing():
    ctx = _ctx(_trace(
        _plane(1, "/device:TPU:0", [("XLA Ops", [("fusion.1", 0, 10)])]),
        _plane(2, "/host:CPU", [("python", [("window", 0, 20)])])))
    assert trace.collective_time(ctx.trace.ops[0], [], 0, 20) == (0, 0)
    assert manifest.metric_reader("device_idle_share")(ctx) == 50.0


def test_host_clock_metrics():
    ctx = _ctx(None, steps=4)
    ctx.spans = [("data", 0.0, 0.002), ("dispatch", 0.002, 0.003)] * 4
    ctx.seconds = 2.0
    assert manifest.metric_reader("input_wait_ms")(ctx) == pytest.approx(2.0)
    # 3e9 FLOP/token x 4096 tokens x 4 steps / 2 s over 4 x 197 TFLOP/s
    assert manifest.metric_reader("step_mfu")(ctx) == pytest.approx(
        100 * 3e9 * 4096 * 4 / 2.0 / (4 * 197e12))


RECORDED = ROOT / "tests/bench/data/small_4chip.xplane.pb"


@pytest.fixture(scope="module")
def recorded():
    """Four steps of a tiny all-reduce program on a TPU v5e 2x2, recorded
    by ``record_trace.py``."""
    return trace.load(str(RECORDED), harness.SPANS)


def test_recorded_trace(recorded):
    assert sorted(recorded.ops) == [0, 1, 2, 3]
    assert [s.name for s in recorded.spans[:4]] == [
        "window", "data", "dispatch", "readback"]
    w = recorded.span("window")
    for d, ops in recorded.ops.items():
        # the all-reduce is called psum_invariant.7: it is known by its
        # opcode, not by its name
        found = [o for o in ops if trace.collective_of(o)]
        assert [o.name for o in found] == ["psum_invariant.7"] * 4
        assert not any(trace.COLLECTIVE.match(o.name) for o in found)
        # the first step's ops lie before the host's window opens on the
        # trace's clock, so three of the four count; each all-reduce runs
        # alone, so all of it is exposed
        inside = trace.clip([(o.start, o.end) for o in found], w.start, w.end)
        total, exposed = trace.collective_time(ops, [], w.start, w.end)
        assert len(inside) == 3 and total == exposed == trace.length(inside)
        assert 0 < trace.busy(ops, w.start, w.end) < (w.end - w.start) / 1000


@pytest.mark.parametrize("name,value", [
    ("device_idle_share", 100 * (1 - 23729 / 116076755)),   # device 3
])
def test_recorded_metrics(recorded, name, value):
    w = recorded.span("window")
    ctx = harness.Context(manifest.load_cell(CELL),
                          recorded, w.start, w.end, [], 4, 1.0, 4096, 3.0e9,
                          4, {"bf16_flops_per_s": 197e12})
    assert manifest.metric_reader(name)(ctx) == pytest.approx(value)
