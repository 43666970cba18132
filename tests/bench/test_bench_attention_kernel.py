"""``attention_kernel_ms``: the flash-attention kernels' device time, read
from a names map and a trace written by hand.

  flash_attention_fwd.1  .../attention/.../flash_attention_fwd/pallas_call
  fusion.2               .../attention/.../reduce_sum (not the kernel)
  flash_attention_dq.3   .../transpose(...)/.../attention/.../pallas_call
  flash_attention_dkv.4  .../transpose(...)/.../attention/.../pallas_call
  pallas_call.5          .../grad_sync/bucket_0/pallas_call (no attention)
  copy.6                 no metadata, so not in the map

Device 0, ns: the four attention ops [0, 100), [100, 150), [150, 230),
[230, 350); pallas_call.5 [350, 400); copy.6 [400, 430).  Device 1:
flash_attention_fwd.1 [0, 60).  The kernels take 100 + 80 + 120 = 300 ns
on device 0 and 60 on device 1: with 2 traced steps over 2 chips,
(300 + 60) / 2 / 2 = 90 ns = 9e-5 ms per step.  ``attention`` as a whole
is (350 + 60) / 4 = 102.5 ns.
"""

import pytest

from benchmarks.chip import harness, manifest, scopes, trace

FWD = ("jit(step)/while/body/closed_call/jvp(forward)/while/body/"
       "closed_call/attention/jit(flash_attention)/flash_attention_fwd/"
       "pallas_call")
BWD = ("jit(step)/while/body/closed_call/transpose(jvp(forward))/while/body/"
       "closed_call/checkpoint/attention/jit(flash_attention)/")
NAMES = {
    "flash_attention_fwd.1": FWD,
    "fusion.2": BWD + "reduce_sum",
    "flash_attention_dq.3": BWD + "flash_attention_dq/pallas_call",
    "flash_attention_dkv.4": BWD + "flash_attention_dkv/pallas_call",
    "pallas_call.5": "jit(step)/grad_sync/bucket_0/pallas_call",
}
OPS0 = [("flash_attention_fwd.1", 0, 100), ("fusion.2", 100, 150),
        ("flash_attention_dq.3", 150, 230), ("flash_attention_dkv.4", 230, 350),
        ("pallas_call.5", 350, 400), ("copy.6", 400, 430)]


def _ops(events):
    return [trace.Op(n, a, b, n) for n, a, b in events]


@pytest.fixture
def ctx():
    tz = trace.Trace({0: _ops(OPS0), 1: _ops([("flash_attention_fwd.1", 0,
                                                60)])},
                     {}, [trace.Op("window", 0, 430)])
    cell = manifest.load_cell("qwen2-1.5b-f32.train4k-fill")
    return harness.Context(cell, tz, 0, 430, [], 2, 1.0, 4096, 3.0e9, 2,
                           {"bf16_flops_per_s": 197e12})


@pytest.fixture
def names(monkeypatch):
    out = {}
    monkeypatch.setattr(scopes, "traced_op_names", lambda ctx: out)
    return out


def test_kernel_names_keep_attention_pallas_calls_only():
    read = manifest._module(manifest.HERE / "metrics"
                            / "attention_kernel_ms.py")
    assert read.kernel_names(NAMES) == {
        k: NAMES[k] for k in ("flash_attention_fwd.1", "flash_attention_dq.3",
                              "flash_attention_dkv.4")}
    for path in (NAMES["flash_attention_dq.3"], NAMES["flash_attention_dkv.4"]):
        assert scopes.classify(path) == "backward"


def test_reads_the_kernels_time_per_step(ctx, names):
    names.update(NAMES)
    read = manifest.metric_reader("attention_kernel_ms")
    assert read(ctx) == pytest.approx(9e-5)
    assert manifest.metric_reader("attention_ms")(ctx) == pytest.approx(
        1.025e-4)


@pytest.mark.parametrize("kept", [
    [],                                  # no op names at all
    ["fusion.2"],                        # the scan: attention, no kernel
    ["pallas_call.5"],                   # a kernel outside attention
])
def test_reads_none_without_an_attention_kernel(ctx, names, kept):
    names.update({k: NAMES[k] for k in kept})
    assert manifest.metric_reader("attention_kernel_ms")(ctx) is None


def test_listed_for_the_cell_that_runs_the_kernel():
    cell = manifest.load_cell("qwen2-1.5b-f32.train4k-fill")
    layers = {m["name"]: m["layer"] for m in cell.per_layer}
    assert layers["attention_kernel_ms"] == layers["attention_ms"]
