"""The benchmark's arithmetic over the whole window, and the reduction from
a profiler trace to device-busy intervals, attribution and breakdown."""
import glob
import os

import numpy as np
import pytest

from bench import harness, stats, trace_reduce
from bench.trace_reduce import Events, Op, Span

TESTDATA = os.path.join(harness.BENCH, "testdata")


def test_percentiles_take_every_release():
    # 20 releases of 10 ms: one slow release must move p95, and nothing
    # is a median of chunks
    resp = [0.002] * 19 + [0.050]
    out = stats.release_responses(0.010, 0.200, resp)
    assert len(out) == 20
    assert stats.percentile(out, 50) == pytest.approx(0.002)
    assert stats.percentile(out, 95) == pytest.approx(
        np.percentile(out, 95))
    assert stats.percentile(out, 95) > 0.002


def test_unfinished_releases_are_censored_at_the_close():
    # 10 releases due (0..90 ms); the job finished only the first 7, and
    # release 6 finished after the close: it counts with its wait so far
    finished = [0.001] * 6 + [0.080]
    out = stats.release_responses(0.010, 0.100, finished)
    assert len(out) == 10
    assert out[6] == pytest.approx(0.100 - 0.060)
    assert out[7:] == pytest.approx([0.030, 0.020, 0.010])
    assert stats.releases_due(0.010, 0.100) == 10
    assert stats.releases_due(0.010, 0.1001) == 11


def test_deadline_share_counts_unfinished_as_missed():
    dl = stats.deadline_met(0.010, 0.100, [0.001] * 7 + [0.011])
    # deadlines of releases 0..9 fall inside the window; 7 met, release 7
    # missed, releases 8 and 9 never finished
    assert dl == {"due": 10, "met": 7, "share": 0.7}


def test_rate_is_over_the_whole_window():
    segs = [(1, 0.1 * i, 0.1 * i + 0.05) for i in range(10)] + \
        [(1, 0.98, 1.02)]
    m, attempted, failed = harness.end_to_end(0.5, 1.0, [0.01, 0.01], segs)
    assert m["be_quanta_per_s"] == 10.0       # the quantum past the close
    assert attempted == 2 and failed == 0


def _synthetic():
    dev = "/device:TPU:0"
    ops = [Op(0.10, 0.30, "fusion", "jit_dave2_forward", dev),
           Op(0.25, 0.40, "dot", "jit_be_hbm", dev),
           Op(0.60, 0.70, "fusion", "jit_dave2_forward", dev),
           Op(0.95, 1.20, "dot", "jit_be_hbm", dev)]
    spans = [Span(0.0, 1.0, "bench.window", "main"),
             Span(0.05, 0.35, "rt.quantum", "lane0"),
             Span(0.55, 0.75, "rt.quantum", "lane0"),
             Span(0.56, 0.74, "dave2.forward", "lane0"),
             Span(0.40, 0.52, "be.hbm", "lane1")]
    return Events(ops=ops, spans=spans, devices=[dev])


def test_reduce_busy_idle_and_rt_attribution():
    r = trace_reduce.reduce(_synthetic())
    assert r.window_s == pytest.approx(1.0)
    # union of [0.10,0.40], [0.60,0.70], [0.95,1.00] (clipped)
    assert r.busy_s == pytest.approx(0.30 + 0.10 + 0.05)
    # the DAVE-2 program's time in the window, 0.10-0.30 and 0.60-0.70;
    # the BE program's time does not count
    assert r.rt_spans == 2
    assert r.rt_device_s == pytest.approx(0.30)
    ops = dict(r.device_ops)
    # where two ops overlap the shorter one holds the device: 0.25-0.30
    # goes to the BE dot
    assert ops["jit_dave2_forward:fusion"] == pytest.approx(0.25)
    assert ops["jit_be_hbm:dot"] == pytest.approx(0.15 + 0.05)
    assert sum(ops.values()) == pytest.approx(r.busy_s)
    gaps = dict(r.idle_gaps)
    # idle: 0-0.10 (rt.quantum open), 0.40-0.60 (midpoint 0.50: be.hbm),
    # 0.70-0.95 (nothing open)
    assert gaps["rt.quantum"] == pytest.approx(0.10)
    assert gaps["be.hbm"] == pytest.approx(0.20)
    assert gaps["host.other"] == pytest.approx(0.25)
    b = trace_reduce.breakdown(r)
    assert set(b) == {"device_ops", "idle_gaps"}
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


@pytest.mark.parametrize("skew", [0.0, 1e-9])
def test_nested_ops_count_their_own_time_once(skew):
    # ``skew``: the body op's stamp falls a rounding step before its loop's
    dev = "/device:TPU:0"
    ops = [Op(0.10, 0.50, "while.1", "jit_be_hbm", dev),
           Op(0.10 - skew, 0.30, "fusion.2", "jit_be_hbm", dev),
           Op(0.30, 0.45, "fusion.3", "jit_be_hbm", dev),
           Op(0.60, 0.70, "fusion", "jit_dave2_forward", dev)]
    spans = [Span(0.0, 1.0, "bench.window", "main")]
    r = trace_reduce.reduce(Events(ops=ops, spans=spans, devices=[dev]))
    own = dict(r.device_ops)
    assert own["jit_be_hbm:while.1"] == pytest.approx(0.05)
    assert own["jit_be_hbm:fusion.2"] == pytest.approx(0.20)
    assert own["jit_be_hbm:fusion.3"] == pytest.approx(0.15)
    assert sum(own.values()) == pytest.approx(r.busy_s)


def test_union_and_clip():
    assert trace_reduce.union([(0, 1), (0.5, 2), (3, 4)]) == [(0, 2), (3, 4)]
    assert trace_reduce.clip([(0, 2), (3, 4)], 1, 3.5) == [(1, 2), (3, 3.5)]


def test_recorded_trace_loads_and_reduces():
    """A short trace recorded on the chip: three RT and three BE calls
    inside the window span."""
    found = glob.glob(os.path.join(TESTDATA, "**", "*.xplane.pb"),
                      recursive=True)
    assert found, "no recorded trace under bench/testdata"
    ev = trace_reduce.load(found[0])
    assert ev.devices and ev.ops
    names = [s.name for s in ev.spans]
    assert names.count("rt.quantum") == 3 and names.count("be.hbm") == 3
    assert names.count("bench.window") == 1
    r = trace_reduce.reduce(ev)
    assert 0 < r.busy_s <= r.window_s
    assert r.rt_spans == 3 and 0 < r.rt_device_s <= r.busy_s
    assert any(n.startswith("jit_be_") for n, _ in r.device_ops)
