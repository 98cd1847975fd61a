"""The reduction from an ``.xplane.pb`` to numbers: exact arithmetic on a
synthetic trace written here as a text proto, and the conventions (plane and
line names, nesting, the anchor) on a small trace recorded on a TPU v5e
(``record_fixture.py``)."""

import os

import pytest

from benchmark.harness import intervals as iv
from benchmark.harness import xplane
from benchmark.harness.spans import OUTSIDE, attribute_gaps

DATA = os.path.join(os.path.dirname(__file__), "data")

# two chips; times in ps from the line's timestamp_ns = 1000 ns.
# chip 0: program A 0..400 us holding a while 0..400 with children
#   fusion.1 0..100, all-reduce.1 100..200 (compute fusion.2 150..180 hides
#   30 us of it), fusion.1 300..400; then idle 400..600; program B 600..700
# chip 1: program A 0..200 with one fusion 0..200.
TEXT = """
planes { id: 1 name: "/device:TPU:0"
  event_metadata { key: 1 value { id: 1 name: "jit_greedy_assign_device(1)" } }
  event_metadata { key: 2 value { id: 2 name: "jit_other(2)" } }
  event_metadata { key: 3 value { id: 3 name: "while.1" } }
  event_metadata { key: 4 value { id: 4 name: "fusion.1" } }
  event_metadata { key: 5 value { id: 5 name: "all-reduce.1" } }
  event_metadata { key: 6 value { id: 6 name: "fusion.2" } }
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 400000000 }
    events { metadata_id: 2 offset_ps: 600000000 duration_ps: 100000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 400000000 }
    events { metadata_id: 4 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 5 offset_ps: 100000000 duration_ps: 100000000 }
    events { metadata_id: 4 offset_ps: 300000000 duration_ps: 100000000 }
    events { metadata_id: 4 offset_ps: 600000000 duration_ps: 100000000 } }
  lines { id: 3 name: "XLA Ops overlap" timestamp_ns: 1000 }
}
planes { id: 2 name: "/device:TPU:1"
  event_metadata { key: 1 value { id: 1 name: "jit_greedy_assign_device(1)" } }
  event_metadata { key: 4 value { id: 4 name: "fusion.1" } }
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 200000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 4 offset_ps: 0 duration_ps: 200000000 } }
}
planes { id: 3 name: "/host:CPU"
  event_metadata { key: 1 value { id: 1 name: "benchmark-anchor" } }
  lines { id: 7 name: "controller" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 } }
}
"""
US = 1e-6


@pytest.fixture(scope="module")
def synthetic():
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(TEXT)


def test_anchor_is_found_on_the_host_plane(synthetic):
    start, end = xplane.annotation(synthetic, "benchmark-anchor")
    assert start == pytest.approx(1000e-9)
    assert end - start == pytest.approx(1 * US)
    assert xplane.annotation(synthetic, "absent") is None


def test_busy_idle_and_programs(synthetic):
    lo = 1000e-9
    r = xplane.reduce_trace(synthetic, (lo, lo + 1000 * US),
                            "greedy_assign_device")
    c0, c1 = r["chips"]
    assert c0["busy_s"] == pytest.approx(500 * US)      # 0..400 and 600..700
    assert c1["busy_s"] == pytest.approx(200 * US)
    assert r["busy_s"] == pytest.approx(350 * US)       # mean over the chips
    assert c0["idle_share"] == pytest.approx(0.5)
    assert r["window_s"] == pytest.approx(1000 * US)
    # the assign program: 400 us on chip 0 and 200 us on chip 1, one run each
    assert r["assign_runs"] == 1
    assert r["assign_s"] == pytest.approx(300 * US)
    assert r["module_s"]["jit_other(2)"] == pytest.approx(50 * US)


def test_self_time_leaves_out_the_children(synthetic):
    lo = 1000e-9
    r = xplane.reduce_trace(synthetic, (lo, lo + 1000 * US))
    ops = r["op_self_s"]                   # means over two chips
    assert ops["while.1"] == pytest.approx(100 * US / 2)   # 400 - 300 inside
    assert ops["fusion.1"] == pytest.approx((300 + 200) * US / 2)
    assert ops["all-reduce.1"] == pytest.approx(100 * US / 2)
    assert xplane.top(ops, 1) == [["fusion.1", pytest.approx(250 * US)]]
    # the trace names an operation by its whole HLO line
    assert xplane.short_name(
        "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %all-reduce.1), kind=kLoop"
    ) == "fusion.2"


def test_collective_time_and_its_exposed_part(synthetic):
    lo = 1000e-9
    r = xplane.reduce_trace(synthetic, (lo, lo + 1000 * US))
    c0 = r["chips"][0]
    assert c0["collective_s"] == pytest.approx(100 * US)
    # no compute LEAF overlaps it in this trace (fusion.2 is not on a line)
    assert c0["collective_exposed_s"] == pytest.approx(100 * US)


def test_the_window_clips_events(synthetic):
    lo = 1000e-9
    r = xplane.reduce_trace(synthetic, (lo + 50 * US, lo + 650 * US))
    assert r["chips"][0]["busy_s"] == pytest.approx((350 + 50) * US)
    assert r["window_s"] == pytest.approx(600 * US)


def test_idle_gaps_go_to_the_span_that_covers_them(synthetic):
    lo = 1000e-9
    r = xplane.reduce_trace(synthetic, (lo, lo + 1000 * US))
    gaps = iv.gaps(r["chips"][0]["busy"], *r["window"])
    spans = {"encode": [(lo + 400 * US, lo + 500 * US)],
             "scheduling-cycle": [(lo + 350 * US, lo + 550 * US)],
             "bind": [(lo + 520 * US, lo + 800 * US)]}
    got = attribute_gaps(gaps, spans)
    assert got["encode"] == pytest.approx(100 * US)
    assert got["in a cycle but in no span"] == pytest.approx(50 * US)
    assert got["bind"] == pytest.approx((50 + 100) * US)   # 550..600, 700..800
    assert got[OUTSIDE] == pytest.approx(200 * US)         # 800..1000


def test_the_loop_s_own_spans_split_what_lies_outside_a_cycle():
    """One iteration, 0..900: pump, then a cycle that holds an encode with
    its encode-spread, an explain and a bind-dispatch, then a drain; the
    device is idle throughout. A nested span takes its part before the span
    that holds it, and the iteration what no other span covers."""
    spans = {"loop-iteration": [(0.0, 900.0)],
             "pump": [(0.0, 100.0)],
             "scheduling-cycle": [(100.0, 700.0)],
             "encode": [(150.0, 300.0)],
             "encode-spread": [(200.0, 250.0)],
             "explain": [(500.0, 560.0)],
             "bind-dispatch": [(560.0, 650.0)],
             "drain": [(720.0, 800.0)],
             "bind": [(600.0, 950.0)]}
    got = attribute_gaps([(0.0, 1000.0)], spans)
    assert got == {
        "encode-spread": pytest.approx(50.0),
        "encode": pytest.approx(100.0),
        "explain": pytest.approx(60.0),
        "bind-dispatch": pytest.approx(90.0),
        "in a cycle but in no span": pytest.approx(600.0 - 300.0),
        "drain": pytest.approx(80.0),
        "pump": pytest.approx(100.0),
        "bind": pytest.approx(20.0 + 150.0),     # 700..720, 800..950
        OUTSIDE: pytest.approx(50.0),            # 950..1000
    }
    # an iteration whose binds have all come back: its own share shows
    spans["bind"] = [(600.0, 700.0)]
    got = attribute_gaps([(0.0, 1000.0)], spans)
    assert got["in an iteration but in no span"] == pytest.approx(120.0)
    assert got[OUTSIDE] == pytest.approx(100.0)
    assert "bind" not in got


def test_a_trace_without_a_device_plane_is_refused():
    from jax.profiler import ProfileData

    host_only = ProfileData.from_text_proto(
        'planes { id: 3 name: "/host:CPU" }')
    with pytest.raises(ValueError, match="no /device:TPU"):
        xplane.reduce_trace(host_only)


def test_recorded_tpu_trace():
    """The conventions the reduction relies on, on a real trace."""
    path = os.path.join(DATA, "tpu_v5e_small.xplane.pb")
    data = xplane.load(path)
    anchor = xplane.annotation(data, "benchmark-anchor")
    assert anchor is not None
    r = xplane.reduce_trace(data, None, "fixture_program")
    assert len(r["chips"]) == 1
    assert r["assign_runs"] == 3            # the recorder ran it three times
    assert 0 < r["busy_s"] < r["window_s"]
    # the programs hold the operations: ops never run outside a program
    assert r["busy_s"] == pytest.approx(sum(r["module_s"].values()),
                                        rel=0.05)
    # a scan is a while that holds other operations: self time < its span
    assert any("while" in name for name in r["op_self_s"])
    assert sum(r["op_self_s"].values()) <= r["busy_s"] * 1.001
    # the device's clock runs a millisecond or so off the host's: the first
    # program is stamped BEFORE the anchor the host wrote ahead of it
    assert -0.005 < r["window"][0] - anchor[0] < 0.005


def test_bytes_and_file_reduce_alike():
    """The harness takes a run's trace as bytes from its profiler session
    (``phases.stop_profiler``); the fixture's recorder and ``tools/`` read
    the file ``jax.profiler.stop_trace`` writes. One XSpace either way: the
    same planes, the same anchor, the same reduction to the last digit."""
    from jax.profiler import ProfileData

    path = os.path.join(DATA, "tpu_v5e_small.xplane.pb")
    with open(path, "rb") as f:
        from_bytes = ProfileData.from_serialized_xspace(f.read())
    from_file = xplane.load(path)
    assert [p.name for p in from_bytes.planes] == \
        [p.name for p in from_file.planes]
    assert xplane.annotation(from_bytes, "benchmark-anchor") == \
        xplane.annotation(from_file, "benchmark-anchor")
    whole = xplane.reduce_trace(from_file, None, "fixture_program")
    assert xplane.reduce_trace(from_bytes, None, "fixture_program") == whole
    # and cut to a window, as a run cuts it
    s, e = whole["window"]
    cut = (s + 0.25 * (e - s), e - 0.25 * (e - s))
    assert xplane.reduce_trace(from_bytes, cut, "fixture_program") == \
        xplane.reduce_trace(from_file, cut, "fixture_program")
