"""The per-layer metrics read from the program's flight record: each
returns its number on a synthetic record, and None where the program
published none."""
import math

import pytest

from bench import harness
from repro.obs import flight

NAN = math.nan
READERS = ("rt_pick_lag_ms", "rt_gate_ms", "be_denied_windows",
           "host_stall_ms", "host_gc_ms")


def reader(name):
    import os
    return harness.load_module(
        os.path.join(harness.BENCH, "metrics", name + ".py"),
        f"bench_metric_{name}")


def view(window_s=1.0):
    return harness.RunView(window_s=window_s, period_s=0.02,
                           rt_segments=[], rt_release=[], be_segments=[],
                           be_lanes=(0, 1), quantum_flops=[], peak=None)


def record():
    P, W = flight.Phase, flight.Window
    T, G = flight.Tick, flight.GcPause
    return flight.FlightRecord(
        window_s=1.0, tick_s=0.01,
        releases=[
            # a two-lane gang: lane 1 picks first at release 0
            P("rt", 0, 0, 0.00, 0.004, 0.005, 0.008),
            P("rt", 0, 1, 0.00, 0.002, 0.004, 0.007),
            P("rt", 1, 0, 0.02, 0.021, 0.023, 0.026),
            P("rt", 1, 1, 0.02, 0.022, 0.024, 0.027),
            P("rt", 2, 0, 0.04, 0.046, NAN, NAN),      # still at the gate
            P("rt", 2, 1, 0.04, NAN, NAN, NAN),
            P("rt", 50, 0, 1.00, 1.001, 1.002, 1.003),  # due at the close
        ],
        windows=[W(0, 0, 0.01, 1.0, 2.0, 1, False),
                 W(1, 0, 0.01, 2.0, 2.0, 2, True),
                 W(0, 1, 0.02, 2.0, 2.0, 2, True),
                 W(2, 0, 0.01, 0.0, 2.0, 0, True),     # not a BE lane
                 W(1, 99, 1.00, 0.0, 2.0, 0, False),
                 W(1, 100, 1.01, 2.0, 2.0, 2, True)],  # past the close
        ticks=[T(0.01, 0.0001, 0.004, 0.0, 0, 0),
               T(0.02, 0.0305, 0.001, 0.03, 2, 0),
               T(1.00, 0.5, 0.0, 0.0, 0, 0)],          # past the close
        gcs=[G(0, -0.001, 0.001), G(2, 0.5, 0.53), G(1, 0.999, 1.004)])


@pytest.fixture
def published():
    before = flight.last_run()
    flight.publish(record())
    yield
    flight.publish(before)


def test_readers_on_a_synthetic_record(published):
    got = {name: reader(name).read(view()) for name in READERS}
    assert got["rt_pick_lag_ms"] == pytest.approx(2.0)   # 2, 1, 6
    assert got["rt_gate_ms"] == pytest.approx(2.0)       # 1, 2, 2, 2
    assert got["be_denied_windows"] == pytest.approx(50.0)
    assert got["host_stall_ms"] == pytest.approx(30.5)
    assert got["host_gc_ms"] == pytest.approx(1.0 + 30.0 + 1.0)


def test_readers_without_a_record():
    before = flight.last_run()
    flight.publish(None)
    try:
        for name in READERS:
            assert reader(name).read(view()) is None, name
    finally:
        flight.publish(before)


def test_readers_on_a_program_without_the_recorder(monkeypatch):
    """An older program has no ``repro.obs.flight``: nothing to read."""
    import sys
    monkeypatch.setitem(sys.modules, "repro.obs.flight", None)
    for name in READERS:
        assert reader(name).read(view()) is None, name
