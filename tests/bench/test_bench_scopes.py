"""The attribution of device time to the program's named scopes.

A compiled module's text is written by hand in the form XLA prints it, and
a trace in the style of ``test_bench_trace.py``'s, with times chosen so
that every number can be worked out on paper.  The map ``HLO`` gives:

  while.1       jit(step)/while                                   other
  fusion.1      .../jvp(forward)/...                              forward
  fusion.2      .../transpose(jvp(forward))/.../rematted_computation/
                attention/...                                     recompute
  convolution.3 .../transpose(jvp(forward))/.../attention/...     backward
  add.4         .../accumulate/add (a ROOT line)                  backward
  all-reduce.5  jit(step)/grad_sync/bucket_0/psum                 grad_sync
  fusion.6      jit(step)/grad_sync/bucket_1/...                  grad_sync
  fusion.7      jit(step)/optimizer/mul                           optimizer
  copy.8        no metadata, so not in the map                    other
  fusion.9      not in the module                                 other

Device 0, ns: while.1 [0, 400) holding fusion.1 [0, 100), fusion.2
[100, 250), convolution.3 [250, 330) and add.4 [340, 390); then
all-reduce.5 [400, 450), fusion.6 [450, 470), fusion.7 [500, 700), copy.8
[700, 760), fusion.9 [990, 1010).  Device 1: fusion.7 [0, 400).

Self times on device 0: the while 400 - (100 + 150 + 80 + 50) = 20 (the gap
[330, 340) is its own), the rest their lengths.  Over [0, 1010) the classes
are forward 100, recompute 150, backward 80 + 50 = 130, grad_sync 50 + 20 =
70, optimizer 200, other 20 + 60 + 20 = 100: 750 ns, which is the busy time
470 + 260 + 20.  Over [0, 1000) fusion.9 runs past the window and does not
count: other 80, 730 ns.  ``attention`` is 150 + 80 = 230 whatever the
class; ``bucket_0`` 50, ``bucket_1`` 20.

With 2 traced steps over 2 chips and the window [0, 1010): optimizer
(200 + 400) / 2 / 2 = 150 ns = 1.5e-4 ms per step; forward (100 + 0) / 2 /
2 = 25 ns.
"""

import re

import pytest

from benchmarks.chip import harness, manifest, scopes, trace

HLO = r"""HloModule jit_step, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation.1 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %multiply.1 = f32[8]{0} multiply(f32[8]{0} %param_0.1, f32[8]{0} %param_0.1), metadata={op_type="mul" op_name="jit(step)/while/body/closed_call/jvp(forward)/mul" source_file="m.py" source_line=3}
}

%body.1 (p.1: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p.1 = (s32[], f32[8]{0}) parameter(0)
  %gte.1 = f32[8]{0} get-tuple-element((s32[], f32[8]{0}) %p.1), index=1
  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %gte.1), kind=kLoop, calls=%fused_computation.1, metadata={op_type="mul" op_name="jit(step)/while/body/closed_call/jvp(forward)/mul" source_file="m.py" source_line=3}
  %fusion.2 = f32[8]{0} fusion(f32[8]{0} %fusion.1), kind=kOutput, calls=%fused_computation.1, metadata={op_type="dot_general" op_name="jit(step)/while/body/closed_call/transpose(jvp(forward))/while/body/closed_call/checkpoint/rematted_computation/attention/dot_general"}
  %convolution.3 = f32[8]{0} convolution(f32[8]{0} %fusion.2, f32[8]{0} %gte.1), dim_labels=b0f_0io->b0f, metadata={op_type="dot_general" op_name="jit(step)/while/body/closed_call/transpose(jvp(forward))/while/body/closed_call/checkpoint/attention/dot_general"}
  %gte.0 = s32[] get-tuple-element((s32[], f32[8]{0}) %p.1), index=0
  ROOT %add.4 = f32[8]{0} add(f32[8]{0} %convolution.3, f32[8]{0} %gte.1), metadata={op_type="add" op_name="jit(step)/while/body/closed_call/accumulate/add"}
}

ENTRY %main.2 (Arg_0.1: f32[8]) -> f32[8] {
  %Arg_0.1 = f32[8]{0} parameter(0), metadata={op_name="state.params[\'embed\']"}
  %while.1 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t), condition=%cond.1, body=%body.1, metadata={op_type="while" op_name="jit(step)/while"}
  %all-reduce.5 = f32[8]{0} all-reduce(f32[8]{0} %Arg_0.1), replica_groups={}, to_apply=%add, metadata={op_type="psum" op_name="jit(step)/grad_sync/bucket_0/psum"}
  %fusion.6 = f32[8]{0} fusion(f32[8]{0} %all-reduce.5), kind=kLoop, calls=%fused_computation.1, metadata={op_type="pad" op_name="jit(step)/grad_sync/bucket_1/jit(_pad)/pad"}
  %fusion.7 = f32[8]{0} fusion(f32[8]{0} %fusion.6), kind=kLoop, calls=%fused_computation.1, metadata={op_type="mul" op_name="jit(step)/optimizer/mul"}
  ROOT %copy.8 = f32[8]{0} copy(f32[8]{0} %fusion.7)
}
"""

NAMES = {
    "multiply.1": "jit(step)/while/body/closed_call/jvp(forward)/mul",
    "fusion.1": "jit(step)/while/body/closed_call/jvp(forward)/mul",
    "fusion.2": "jit(step)/while/body/closed_call/transpose(jvp(forward))/"
                "while/body/closed_call/checkpoint/rematted_computation/"
                "attention/dot_general",
    "convolution.3": "jit(step)/while/body/closed_call/"
                     "transpose(jvp(forward))/while/body/closed_call/"
                     "checkpoint/attention/dot_general",
    "add.4": "jit(step)/while/body/closed_call/accumulate/add",
    "Arg_0.1": r"state.params[\'embed\']",
    "while.1": "jit(step)/while",
    "all-reduce.5": "jit(step)/grad_sync/bucket_0/psum",
    "fusion.6": "jit(step)/grad_sync/bucket_1/jit(_pad)/pad",
    "fusion.7": "jit(step)/optimizer/mul",
}

OPS0 = [("%while.1 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t)", 0,
         400),
        ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %gte.1)", 0, 100),
        ("fusion.2", 100, 250), ("convolution.3", 250, 330),
        ("add.4", 340, 390), ("all-reduce.5", 400, 450),
        ("fusion.6", 450, 470), ("fusion.7", 500, 700),
        ("%copy.8 = f32[8]{0} copy(f32[8]{0} %fusion.7)", 700, 760),
        ("fusion.9", 990, 1010)]


def _ops(events):
    ops = [trace.Op(trace.op_name(t), a, b, t) for t, a, b in events]
    return sorted(ops, key=lambda o: (o.start, -o.end))


@pytest.fixture
def hand():
    return trace.Trace({0: _ops(OPS0), 1: _ops([("fusion.7", 0, 400)])},
                       {}, [trace.Op("window", 0, 1010)])


def _ctx(tz, cell=None):
    cell = cell or manifest.load_cell("qwen2-1.5b-f32.train4k-fill")
    return harness.Context(cell, tz, 0, 1010, [], 2, 1.0, 4096, 3.0e9, 2,
                           {"bf16_flops_per_s": 197e12})


def test_op_names_parses_instructions_roots_and_fusions():
    assert scopes.op_names(HLO) == NAMES
    assert scopes.op_names("ROOT %x = f32[] add(%a, %b)") == {}


@pytest.mark.parametrize("path,cls", [
    ("jit(step)/grad_sync/bucket_3/psum", "grad_sync"),
    # the ZeRO-3 gather in the forward pass, and its transpose
    ("jit(step)/while/body/jvp(grad_sync)/all_gather", "grad_sync"),
    ("jit(step)/transpose(jvp(grad_sync))/reduce_scatter", "grad_sync"),
    # grad_sync wins over the optimizer around it
    ("jit(step)/optimizer/grad_sync/bucket_0/pad", "grad_sync"),
    ("jit(step)/optimizer/jit(clip)/mul", "optimizer"),
    ("jit(step)/transpose(jvp(forward))/checkpoint/rematted_computation/"
     "dot_general", "recompute"),
    ("jit(step)/transpose(jvp(forward))/checkpoint/dot_general", "backward"),
    ("jit(step)/while/body/closed_call/accumulate/add", "backward"),
    ("jit(step)/while/body/jvp(forward)/while/body/dot_general", "forward"),
    ("jit(step)/forward/dot_general", "forward"),
    # a transpose op is no transposition; an unscoped hoisted mask
    ("jit(step)/transpose", "other"),
    ("jit(step)/while/body/closed_call/attention/closed_call/and", "other"),
    ("jit(step)/forwarding/jvp(forwards)/add", "other"),
    (None, "other"),
])
def test_classify(path, cls):
    assert scopes.classify(path) == cls


def test_scope_names_unwrap_transformations():
    assert scopes.scope_names("a/transpose(jvp(forward))/b") == {
        "a", "transpose(jvp(forward))", "jvp(forward)", "forward", "b"}


def test_scope_times_partition_the_busy_time(hand):
    ops = hand.ops[0]
    times = scopes.scope_times(ops, NAMES, 0, 1010)
    assert times == {"forward": 100, "recompute": 150, "backward": 130,
                     "grad_sync": 70, "optimizer": 200, "other": 100}
    assert sum(times.values()) == trace.busy(ops, 0, 1010) == 750


def test_ops_past_the_window_do_not_count(hand):
    times = scopes.scope_times(hand.ops[0], NAMES, 0, 1000)
    assert times["other"] == 80 and sum(times.values()) == 730


def test_while_is_not_counted_twice(hand):
    # the while's 400 ns hold its body's 380; only its own 20 are other
    body = scopes.scope_times(hand.ops[0][:5], NAMES, 0, 400)
    assert body == {"forward": 100, "recompute": 150, "backward": 130,
                    "grad_sync": 0, "optimizer": 0, "other": 20}


def test_scope_and_bucket_times(hand):
    ops = hand.ops[0]
    assert scopes.scope_time(ops, NAMES, "attention", 0, 1010) == 230
    assert scopes.scope_time(ops, NAMES, "bucket_1", 0, 1010) == 20
    assert scopes.bucket_times(ops, NAMES, 0, 1010) == {0: 50, 1: 20}
    assert scopes.other_ops(ops, NAMES, 0, 1010, top=2) == [
        ("%copy.8 = f32[8]{0} copy(f32[8]{0} %fusion.7)", 60),
        ("%while.1 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t)", 20)]


@pytest.mark.parametrize("name,value", [
    ("forward_ms", 25e-6), ("backward_ms", 32.5e-6),
    ("recompute_ms", 37.5e-6), ("optimizer_ms", 150e-6),
    ("grad_sync_ms", 17.5e-6), ("attention_ms", 57.5e-6)])
def test_metrics(hand, monkeypatch, name, value):
    read = manifest.metric_reader(name)
    names = {}
    monkeypatch.setattr(scopes, "traced_op_names", lambda ctx: names)
    names.update(NAMES)
    assert read(_ctx(hand)) == pytest.approx(value)
    # nothing to read without the map, or from a program without the scope
    names.clear()
    assert read(_ctx(hand)) is None
    names["fusion.9"] = "jit(step)/mul"
    assert read(_ctx(hand)) is None


def test_report(hand):
    lines = scopes.report(_ctx(hand), NAMES)
    # ns per step per chip: bucket_0 50 / 2 / 2, bucket_1 20 / 2 / 2
    assert lines[:2] == ["bucket 0: 1.25e-05 ms/step",
                         "bucket 1: 5e-06 ms/step"]
    assert lines[2].startswith("other: 2.5e-05 ms/step")
    # the classes, (750 + 400) / 4 ns, are the busy time; of it only
    # copy.8 and fusion.9 have no name in the map: (1150 - 80) / 4 ns
    assert lines[-1] == ("scopes: six classes sum to 0.0002875 ms/step "
                         "against busy 0.0002875 ms/step; 0.0002675 ms/step "
                         "of ops whose instruction has an op_name in the "
                         "program's text")
    assert scopes.report(_ctx(hand), {}) == [
        "scopes: no op names or no device ops; no attribution"]


def test_traced_op_names_compiles_once_and_reports(hand, monkeypatch,
                                                   capsys):
    calls = []
    monkeypatch.setattr(scopes, "_TRACED", {})
    monkeypatch.setattr(scopes, "compile_text",
                        lambda cell, chips: calls.append(chips) or HLO)
    ctx = _ctx(hand)
    assert scopes.traced_op_names(ctx) == NAMES
    assert scopes.traced_op_names(ctx) is scopes.traced_op_names(ctx)
    assert calls == [2]
    err = capsys.readouterr().err
    assert "scopes: 10 op names" in err and "bucket 1: 5e-06" in err

    def fails(cell, chips):
        raise RuntimeError("no compiler")
    monkeypatch.setattr(scopes, "compile_text", fails)
    ctx.cell.traffic["seq_len"] = 8         # another program
    assert scopes.traced_op_names(ctx) == {}
    assert "RuntimeError: no compiler" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# The program's own scopes, in its step compiled for one CPU device.
# ---------------------------------------------------------------------------

PHASES = {"forward", "backward", "recompute"}
DOT = re.compile(r"%([\w.\-]+) = [^\n]*? (?:dot|convolution)\(")


@pytest.fixture(scope="module")
def step():
    """(compiled text, its op names, buckets of the plan) of the benchmark
    cell's step at a CPU size: ZeRO-1, ``remat="block"``, 2 microbatches
    of 1, the ``mgwfbp`` plan, and the chunked attention scan (64 tokens
    in chunks of 32)."""
    import jax

    from conftest import tiny_cell

    cell = tiny_cell("qwen2-1.5b-f32.train4k-fill")
    cell.traffic.update(batch_per_chip=2)
    cell.config["parallel"]["attn_chunk"] = 32
    b = harness.build(cell, jax.devices()[:1])
    par = b.run.parallel
    assert (par.zero, par.remat, par.comm_strategy) == (1, "block", "mgwfbp")
    text = scopes.compile_text(cell, 1)
    return text, scopes.op_names(text), b.art.plan.num_buckets


def _users(text, name):
    return re.findall(rf"^\s*(?:ROOT )?%([\w.\-]+) = [^\n]*%{re.escape(name)}"
                      r"(?![\w.\-])", text, re.M)


def test_every_matmul_is_in_a_phase(step):
    """Every dot and convolution, fused or not, is forward, backward or
    recompute.  XLA:CPU's dot decomposer rewrites a dot with several batch
    dimensions (the attention's einsums) into a new dot that carries no
    metadata; such a dot is judged by the instructions that consume it.
    (``test_tpu_compile.py`` holds the TPU compiler, which keeps every
    dot's metadata, to the rule with no such exception.)"""
    text, names, _ = step
    dots = DOT.findall(text)
    assert len(dots) >= 10
    for n in dots:
        if n in names:
            assert scopes.classify(names[n]) in PHASES, (n, names[n])
        else:
            users = _users(text, n)
            assert users and {scopes.classify(names.get(u))
                              for u in users} <= PHASES, (n, users)


def test_every_scope_holds_an_instruction(step):
    _, names, buckets = step
    paths = list(names.values())
    assert buckets >= 2
    for cls in PHASES | {"optimizer"}:
        assert any(scopes.classify(p) == cls for p in paths), cls
    for scope in ["accumulate", "attention"] + [f"bucket_{k}"
                                                 for k in range(buckets)]:
        assert any(scope in scopes.scope_names(p) for p in paths), scope
    for k in range(buckets):
        assert any({"grad_sync", f"bucket_{k}"} <= scopes.scope_names(p)
                   for p in paths), k
