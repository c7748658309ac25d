"""The trace reduction: busy union, idle share, idle gaps named by the
host span over them, per-name time; on a small
hand-written trace and on a small trace recorded on a TPU v5e."""

from pathlib import Path

import pytest
from jax.profiler import ProfileData

from bench import trace as tr

DATA = Path(__file__).resolve().parent / "data"


def _events(pairs):
    return "\n".join(f"    events {{ metadata_id: {m} offset_ps: {s * 1000} "
                     f"duration_ps: {d * 1000} }}" for m, s, d in pairs)


def _plane(pid, name, line, pairs, names):
    meta = "\n".join(f'  event_metadata {{ key: {i} value {{ id: {i} '
                     f'name: "{n}" }} }}' for i, n in names.items())
    return (f'planes {{\n  id: {pid}\n  name: "{name}"\n  lines {{\n'
            f'    id: 1\n    name: "{line}"\n    timestamp_ns: 0\n'
            f'{_events(pairs)}\n  }}\n{meta}\n}}\n')


#: two chips over [0, 100] ns.  Chip 0: ops 0-20, 10-30 (overlapping),
#: 50-60, an all-gather 60-90 half hidden by an op 75-100.  Chip 1: one
#: op 0-100.  Host: bench.round over 0-45, bench.wait over 30-50.
TEXT = (
    _plane(1, "/device:TPU:0", "XLA Ops",
           [(1, 0, 20), (2, 10, 20), (1, 50, 10), (3, 60, 30), (4, 75, 25)],
           {1: "fusion.1", 2: "topk_compress", 3: "all-gather.2",
            4: "fusion.9"})
    + _plane(2, "/device:TPU:1", "XLA Ops", [(1, 0, 100)],
             {1: "fusion.1"})
    + _plane(4, "/host:CPU", "python", [(1, 0, 45), (2, 30, 20)],
             {1: "bench.round", 2: "bench.wait"}))


@pytest.fixture(scope="module")
def reduced():
    return tr.from_profile(ProfileData.from_text_proto(TEXT), n_devices=2)


def test_busy_union_and_idle_share(reduced):
    assert reduced.window_s == pytest.approx(100e-9)
    # chip 0 busy 0-30 and 50-100 = 80; chip 1 busy 100: mean 90
    assert reduced.busy_s == pytest.approx(90e-9)
    assert reduced.idle_share == pytest.approx(0.1)


def test_idle_gaps_named_by_host_span(reduced):
    # chip 0 idles 30-50: its middle (40) lies in bench.wait (the
    # innermost span there)
    assert reduced.idle_gaps(0) == [("bench.wait", pytest.approx(20e-9))]


def test_per_name_and_matching(reduced):
    per = reduced.per_name_s()
    assert per["fusion.1"] == pytest.approx((20 + 10 + 100) / 2 * 1e-9)
    assert reduced.time_s("topk") == pytest.approx(10e-9)
    bd = reduced.breakdown()
    assert bd["device_ops"][0][0] == "fusion.1"
    assert bd["idle_gaps"][0][0] == "bench.wait"


def test_union_and_gaps():
    assert tr.union([(5, 6), (0, 2), (1, 3)]) == [(0, 3), (5, 6)]
    assert tr.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]


def test_trace_without_device_ops_is_refused():
    host_only = _plane(1, "/host:CPU", "python", [(1, 0, 5)],
                       {1: "bench.round"})
    with pytest.raises(ValueError):
        tr.from_profile(ProfileData.from_text_proto(host_only), 1)


def test_recorded_v5e_trace():
    """A trace recorded on one v5e chip: a matmul-and-tanh program, a
    20 ms host sleep, a reduction program, twice."""
    red = tr.from_profile(
        ProfileData.from_file(str(DATA / "tiny_v5e.xplane.pb")), 1)
    assert red.busy_s > 0
    assert 0.0 < red.idle_share < 1.0
    # the two sleeps are the longest idle gaps, named by their span
    gaps = red.idle_gaps()
    assert [g[0] for g in gaps[:2]] == ["bench.wait", "bench.wait"]
    assert all(g[1] > 0.015 for g in gaps[:2])


def test_self_time_of_nested_operations():
    """A while loop's event encloses its body's operations: its self
    time is what the body leaves uncovered."""
    loop = tr.Event("%while.1", 0, 100, 0)
    body = [tr.Event("fusion.2", 10, 40, 0), tr.Event("fusion.3", 50, 90, 0)]
    got = {e.name: t for e, t in tr.self_times([loop] + body)}
    assert got == {"%while.1": 30, "fusion.2": 30, "fusion.3": 40}
