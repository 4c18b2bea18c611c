"""``trace_reduce.py`` against ``data/tiny_trace.xplane.pb``, whose intervals
are written out in ``data/tiny_trace.textproto`` (the ``.pb`` is that file
through ``ProfileData.text_proto_to_serialized_xspace``)."""

import os

import pytest

from benchmarks import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
ALL_REDUCE = r"^all-reduce"
MOSAIC = r'custom_call_target="tpu_custom_call"'


START_NS = 1_700_000_000 * 10**9      # the trace's profile_start_time
MS = 10**6


def on_the_wall_clock(spans_ms: dict) -> dict:
    return {name: [(START_NS + a * MS, START_NS + b * MS) for a, b in spans]
            for name, spans in spans_ms.items()}


# What the loop timed, as it hands it over: ns since the epoch.
SPANS_NS = on_the_wall_clock({"dispatch": [(11, 12), (10, 11)],
                              "fence": [(12, 50)]})


@pytest.fixture(scope="module")
def trace():
    return tr.read_xplane(os.path.join(DATA, "tiny_trace.xplane.pb"),
                          SPANS_NS)


def test_recorded_file_is_the_text_proto(trace, tmp_path):
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, "tiny_trace.textproto")) as f:
        built = ProfileData.text_proto_to_serialized_xspace(f.read())
    path = tmp_path / "built.xplane.pb"
    path.write_bytes(built)
    assert tr.read_xplane(str(path), SPANS_NS) == trace


def test_planes_lines_and_names(trace):
    # Only /device:TPU:<n> planes, only their "XLA Ops" line; the name is
    # the instruction's, the rest of XLA's text is kept to match on.
    assert sorted(trace.devices) == ["/device:TPU:0", "/device:TPU:1"]
    names = [o.name for o in trace.devices["/device:TPU:0"]]
    assert names == ["fusion.1", "all-reduce.1", "jvp__.1", "fusion.2",
                     "all-reduce.2", "fusion.3"]
    assert "tpu_custom_call" in trace.devices["/device:TPU:0"][2].tags
    # The loop's spans, on the trace's clock and in order.
    assert [x for span in trace.spans["dispatch"] for x in span] \
        == pytest.approx([0.010, 0.011, 0.011, 0.012])
    assert list(trace.spans["fence"][0]) == pytest.approx([0.012, 0.050])


def test_window_busy_and_idle(trace):
    lo, hi = tr.window_of(trace)
    assert (lo, hi) == pytest.approx((0.010, 0.050))
    busy, window = tr.busy_seconds(trace)
    assert window == pytest.approx(0.040)
    assert busy == pytest.approx((0.030 + 0.020) / 2)     # mean over chips
    chip0 = tr.busy_of(tr.first_device(trace), lo, hi)
    assert tr.total(chip0) == pytest.approx(0.030)
    assert 1 - tr.total(chip0) / window == pytest.approx(0.25)


def test_idle_by_segment():
    # The same trace cut in two segments: [10,30] holds [24,26] idle after
    # the dispatch gap [10,12]; [30,50] holds [38,40] and [44,48].
    trace = tr.read_xplane(
        os.path.join(DATA, "tiny_trace.xplane.pb"),
        on_the_wall_clock({"dispatch": [(10, 11), (30, 31)],
                           "fence": [(12, 30), (31, 50)]}))
    assert tr.idle_by_segment(trace) == pytest.approx([4 / 20, 6 / 20])


def test_a_trace_that_does_not_say_when_it_started(tmp_path):
    from jax.profiler import ProfileData

    path = tmp_path / "bare.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        'planes { id: 1 name: "/device:TPU:0" }'))
    with pytest.raises(ValueError, match="when the profile started"):
        tr.read_xplane(str(path), SPANS_NS)


def test_collective_total_and_exposed(trace):
    assert tr.op_seconds(trace, ALL_REDUCE) == pytest.approx(0.010)
    assert tr.exposed_seconds(trace, ALL_REDUCE) == pytest.approx(0.008)


def test_kernel_time_by_target(trace):
    assert tr.op_seconds(trace, MOSAIC) == pytest.approx(0.004)


def test_breakdown(trace):
    b = tr.breakdown(trace)
    ops = dict(b["device_ops"])
    assert ops == pytest.approx(
        {"fusion": 0.018, "all-reduce": 0.010, "jvp__": 0.004})
    assert [n for n, _ in b["device_ops"]] == ["fusion", "all-reduce",
                                               "jvp__"]
    assert dict(b["idle_gaps"]) == pytest.approx(
        {"fence": 0.008, "dispatch": 0.002})


def test_a_gap_goes_to_what_the_host_began_in_it():
    # The first fence ends at 38; the chip is idle over [38,40], with no
    # span open, and over [44,48]. The loop places and dispatches at
    # 44.5..45.5 and is back in a fence from 45.5, which is the span open at
    # the middle of [44,48]: the gap still goes to ``place``, the first span
    # to start inside it.
    trace = tr.read_xplane(
        os.path.join(DATA, "tiny_trace.xplane.pb"),
        on_the_wall_clock({"dispatch": [(10, 12), (45, 45.5)],
                           "place": [(44.5, 45)],
                           "fence": [(12, 38), (45.5, 50)]}))
    assert dict(tr.breakdown(trace)["idle_gaps"]) == pytest.approx(
        {"dispatch": 0.002, "fence": 0.002, "between_spans": 0.002,
         "place": 0.004})


@pytest.mark.parametrize("a, b, want", [
    ([(0, 10)], [(2, 3), (5, 7)], [(0, 2), (3, 5), (7, 10)]),
    ([(0, 4), (6, 9)], [(3, 7)], [(0, 3), (7, 9)]),
    ([(0, 4)], [], [(0, 4)]),
    ([(0, 4)], [(0, 4)], []),
])
def test_subtract(a, b, want):
    assert tr.subtract(a, b) == want


def test_merge_and_clip():
    assert tr.merge([(5, 6), (0, 2), (1, 3), (3, 3)]) == [(0, 3), (5, 6)]
    assert tr.clip([(0, 3), (5, 9)], 2, 6) == [(2, 3), (5, 6)]
