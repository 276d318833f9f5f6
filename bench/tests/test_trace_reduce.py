"""The trace reduction, on a small trace recorded on a TPU v5e chip and on
hand-made intervals.

The recorded trace (``data/tiny_tpu.xplane.pb``) holds, inside a host
span ``tiny_job``: one 16 MB ``device_put``, three runs of a jitted
``cumsum(sin(x)) * 2`` over 1M floats, a 10 ms sleep, and one more run on
a slice of the copied array (which also compiles ``dynamic_slice``)."""

from pathlib import Path

import pytest

from bench import trace_reduce as tr

TRACE = Path(__file__).parent / "data" / "tiny_tpu.xplane.pb"


@pytest.fixture(scope="module")
def events():
    return tr.read_xspace(str(TRACE))


def test_reads_the_chip_and_the_host(events):
    assert list(events.device) == ["/device:TPU:0"]
    lines = {line for *_, line in events.device["/device:TPU:0"]}
    assert lines == {tr.MODULES, tr.OPS}
    assert any(name == "tiny_job" for *_, name in events.host)


def test_busy_is_the_union_of_the_programs(events):
    """The programs ran one after another, so their union is their sum;
    every operation lies inside its program."""
    evs = events.device["/device:TPU:0"]
    modules = sorted((s, e) for s, e, _, line in evs if line == tr.MODULES)
    assert all(a[1] <= b[0] for a, b in zip(modules, modules[1:]))
    s = tr.summarize(events)
    # (an op may end a nanosecond past its program: rounding in the trace)
    assert s.busy_s == pytest.approx(sum(e - s for s, e in modules) / 1e9,
                                     rel=1e-4)
    assert 0 < s.compute_s <= s.busy_s
    assert s.chips == 1


def test_idle_share_over_the_annotated_window(events):
    s = tr.summarize(events, span="tiny_job")
    job = [(a, b) for a, b, name in events.host if name == "tiny_job"][0]
    assert s.window_s == pytest.approx((job[1] - job[0]) / 1e9)
    assert s.idle_share == pytest.approx(1 - s.busy_s / s.window_s)
    # under a millisecond of device work in a window of over 10 ms
    assert s.busy_s < 1e-3 and s.window_s > 0.01
    assert 95.0 < tr.idle_percent(s) < 100.0


def test_breakdown_names_ops_and_gaps(events):
    s = tr.summarize(events, span="tiny_job")
    names = [name for name, _ in s.device_ops]
    assert names[0] == "jit__lambda:reduce-window"
    assert all(":" in name for name in names)
    assert sum(sec for _, sec in s.device_ops) <= s.busy_s * 1.0001
    gaps = dict(s.idle_gaps)
    assert len(s.idle_gaps) <= 10
    # the idle time is the window less the busy time, all of it named
    assert sum(gaps.values()) == pytest.approx(s.window_s - s.busy_s,
                                               rel=1e-6)
    out = s.breakdown()
    assert set(out) == {"device_ops", "idle_gaps"}


def test_union_merges_nested_and_overlapping_intervals():
    merged = tr.union([(0, 10), (2, 3), (9, 15), (20, 25), (30, 40)], 1, 35)
    assert merged == [(1, 15), (20, 25), (30, 35)]
    assert tr.union([(5, 5), (7, 3)], 0, 10) == []


def test_self_time_leaves_out_enclosed_ops():
    ops = [(0, 100, "m:while"), (10, 40, "m:fusion"), (50, 60, "m:fusion"),
           (55, 58, "m:copy"), (200, 210, "m:fusion")]
    assert tr.self_times(ops) == {"m:while": 60, "m:fusion": 47,
                                  "m:copy": 3}


def test_a_gap_takes_the_innermost_frame_that_spans_it():
    host = [(0, 100, "job"), (10, 60, "outer"), (20, 45, "inner"),
            (44, 46, "blip")]
    assert tr.name_gap(host, 21, 44) == "inner"
    assert tr.name_gap(host, 5, 58) == "outer"
    assert tr.name_gap(host, 61, 99) == "job"
    assert tr.name_gap([], 1, 2) == "(no host frame)"


def test_nothing_traced_reads_nothing():
    assert tr.idle_percent(None) is None
