"""Property-based obs invariants (skipped cleanly without hypothesis).

* histogram merge is associative and exact (fixed exponential buckets:
  a merge is an integer bucket-count sum, so grouping cannot matter);
* snapshot deltas of monotone metrics are non-negative and re-merge to
  the later snapshot;
* flight-recorder JSONL round-trip is the identity on random records.
"""

import json

import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis")

from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.obs import metrics, recorder  # noqa: E402

finite = st.floats(min_value=1e-9, max_value=1e9, allow_nan=False,
                   allow_infinity=False)
times = st.floats(min_value=0.0, max_value=1e6, allow_nan=False,
                  allow_infinity=False)


def _hist_snapshot(values):
    reg = metrics.Registry()
    h = reg.histogram("t", "prop")
    for v in values:
        h.observe(v)
    return reg.snapshot()


def _assert_hists_equal(x: dict | None, y: dict | None):
    """Bucket counts / count / min / max merge EXACTLY (integer sums and
    min/max are associative); the float running ``sum`` is only
    associative up to rounding, so it gets an isclose."""
    if x is None or y is None:
        assert x == y
        return
    for field in ("counts", "count", "min", "max"):
        assert x[field] == y[field], field
    assert x["sum"] == pytest.approx(y["sum"], rel=1e-12, abs=1e-12)


@given(st.lists(finite, max_size=30), st.lists(finite, max_size=30),
       st.lists(finite, max_size=30))
@settings(max_examples=50, deadline=None)
def test_histogram_merge_associative(xs, ys, zs):
    a, b, c = _hist_snapshot(xs), _hist_snapshot(ys), _hist_snapshot(zs)
    left = a.merge(b).merge(c).hist("t")
    right = a.merge(b.merge(c)).hist("t")
    _assert_hists_equal(left, right)
    if left is not None:
        assert left["count"] == len(xs) + len(ys) + len(zs)
        # bucket counts are exact integer sums of the parts
        assert sum(left["counts"].values()) == left["count"]


@given(st.lists(finite, min_size=1, max_size=20),
       st.lists(finite, max_size=20))
@settings(max_examples=50, deadline=None)
def test_snapshot_delta_nonnegative_and_remergeable(first, second):
    reg = metrics.Registry()
    c = reg.counter("n_total", "prop")
    h = reg.histogram("t", "prop")
    for v in first:
        c.inc(v)
        h.observe(v)
    early = reg.snapshot()
    for v in second:
        c.inc(v)
        h.observe(v)
    late = reg.snapshot()
    d = late.delta(early)
    assert d.value("n_total") >= 0.0
    dh = d.hist("t")
    assert dh["count"] == len(second) >= 0
    assert all(n >= 0 for n in dh["counts"].values())
    # merging the delta back reconstructs the later snapshot — exactly
    # for the integer state, to rounding for the float running sums
    rem = early.merge(d)
    assert rem.value("n_total") == \
        pytest.approx(late.value("n_total"), rel=1e-12, abs=1e-12)
    _assert_hists_equal(rem.hist("t"), late.hist("t"))


pairs = st.lists(st.tuples(st.text(alphabet="abcxyz", min_size=1,
                                   max_size=4), times),
                 max_size=3).map(tuple)

bucket_records = st.builds(
    recorder.BucketRecord,
    bucket=st.integers(min_value=0, max_value=99),
    nbytes=st.integers(min_value=0, max_value=1 << 40),
    ready=times, start=times, end=times, comm_s=times)

iteration_records = st.builds(
    recorder.IterationRecord,
    source=st.sampled_from(["sim", "train"]),
    job=st.text(alphabet="abcdef", min_size=1, max_size=6),
    iteration=st.integers(min_value=0, max_value=10**6),
    start=times, end=times, backward_end=times,
    staleness=st.integers(min_value=0, max_value=64),
    buckets=st.lists(bucket_records, max_size=4).map(tuple),
    worker_compute=pairs, worker_start=pairs, worker_end=pairs,
    link_bytes=pairs, link_busy=pairs,
    args=st.dictionaries(st.text(alphabet="abc", min_size=1, max_size=3),
                         st.one_of(times, st.text(max_size=8)),
                         max_size=3))

event_records = st.builds(
    recorder.EventRecord,
    kind=st.sampled_from(["planner_update", "coplan_round", "drift_alert"]),
    time=times,
    source=st.sampled_from(["sim", "planner", "coplanner", "train"]),
    job=st.text(alphabet="abcdef", max_size=6),
    args=st.dictionaries(st.text(alphabet="xyz", min_size=1, max_size=3),
                         times, max_size=3))


@given(records=st.lists(st.one_of(iteration_records, event_records),
                         max_size=20))
@settings(max_examples=50, deadline=None)
def test_recorder_round_trip_identity(records, tmp_path_factory):
    path = tmp_path_factory.mktemp("obs") / "rec.jsonl"
    recorder.write_jsonl(str(path), records)
    back = recorder.read_jsonl(str(path))
    assert back == records
    # and the wire format itself is plain JSON lines
    with open(path) as f:
        for line in f:
            assert json.loads(line)["type"] in ("iteration", "event")
