"""The executor's flight recorder (obs/flight.py): per-release phases,
per-window admission rows, the host-stall probe and the profiler
anchor."""
import gc
import glob
import os
import time

import pytest

from repro.core.executor import BEJob, GangExecutor, RTJob
from repro.core.throttle import BandwidthRegulator
from repro.obs import flight


def test_one_lane_phases_add_up_to_the_response():
    """pick lag + gate + run of each finished release of a one-lane gang
    is its response, with BE quanta blocking the lane and admission
    stalling the RT quantum now and then."""
    ex = GangExecutor(n_lanes=2, regulation_interval_s=0.005)
    ex.submit_rt(RTJob("rt", lambda lane, k: time.sleep(0.001), lanes=(0,),
                       prio=5, period_s=0.01, budget_bytes=2.0,
                       bytes_per_quantum=1.0))
    ex.submit_be(BEJob("be", lambda lane: time.sleep(0.002), lanes=(0, 1),
                       bytes_per_quantum=1.0))
    ex.run(0.6)
    rec = flight.last_run()
    resp = ex.response_times["rt"]
    assert len(resp) >= 30
    rows = {p.k: p for p in rec.releases if p.job == "rt"}
    for k, r in enumerate(resp):
        p = rows[k]
        assert p.lane == 0 and p.due == pytest.approx(k * 0.01)
        assert p.pick_lag >= 0 and p.gate >= 0 and p.run > 0
        assert abs(p.pick_lag + p.gate + p.run - r) <= 1e-6


def test_a_denied_lane_counts_one_window():
    """A lane denied and retried five times in one window yields one
    denied window, and ``denied_total`` counts the denial once."""
    reg = BandwidthRegulator(1, interval=1.0, mode="admission",
                             record_history=True)
    reg.set_core_budgets({0: 1.0})
    assert reg.charge(0, 0.8, 0.1)
    assert reg.charge(0, 0.8, 0.2) is False
    for t in (0.3, 0.4, 0.5, 0.6, 0.7, 0.9):
        assert reg.charge(0, 0.8, t) is False
    assert reg.charge(0, 0.8, 1.1)            # rolls window 0
    rows = flight.windows_from_history(reg.history)
    assert [(w.lane, w.k, w.admitted, w.denied) for w in rows] == \
        [(0, 0, 1, True)]
    assert reg.cores[0].total_denied == pytest.approx(0.8)
    assert reg.cores[0].throttle_events == 1


def test_executor_counts_each_denied_window_once():
    """Best-effort lanes whose budget admits one quantum per window retry
    again and again in each; ``denied_total`` counts one denial per
    denied window, and the window rows say which."""
    ex = GangExecutor(n_lanes=2, regulation_interval_s=0.4)
    ex.submit_be(BEJob("be", lambda lane: time.sleep(0.0005), lanes=(0, 1),
                       bytes_per_quantum=1.0))
    ex.reg.set_core_budgets({}, default=1.0)
    refused = {}
    charge = ex.reg.charge

    def counted(lane, amount, now):
        ok = charge(lane, amount, now)
        if not ok:
            key = (lane, int(now / 0.4))
            refused[key] = refused.get(key, 0) + 1
        return ok

    ex.reg.charge = counted
    ex.run(1.7)
    rec = flight.last_run()
    assert max(refused.values()) >= 5
    for lane in (0, 1):
        rows = [w for w in rec.windows if w.lane == lane]
        assert [w.k for w in rows] == [0, 1, 2, 3]
        assert all(w.admitted == 1 and w.denied for w in rows)
        denied = len(rows) + ex.reg.cores[lane].denied
        assert ex.reg.cores[lane].total_denied == pytest.approx(denied)


def test_window_rows_from_many_lanes_lose_nothing():
    """Lane threads share the regulator's history ring: with more threads
    than cores and a short switch interval, every window a lane closes
    is one row."""
    import sys
    import threading
    n_lanes, n_windows = 16, 400
    reg = BandwidthRegulator(n_lanes, interval=1.0, mode="admission",
                             record_history=True)

    def lane(c):
        for k in range(n_windows + 1):
            reg.charge(c, 0.0, float(k))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=lane, args=(c,))
                   for c in range(n_lanes)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    rows = flight.windows_from_history(reg.history)
    for c in range(n_lanes):
        assert sorted(w.k for w in rows if w.lane == c) == \
            list(range(n_windows))


def test_a_planted_gc_pause_shows_as_a_late_tick():
    """``gc.collect()`` over about a million objects inside a quantum
    holds every thread: the monitor's tick wakes late, overlapping the
    ``host.gc`` row of that collection."""
    junk = [[] for _ in range(10 ** 6)]

    def fn(lane, k):
        if k == 5:
            gc.collect()
        else:
            time.sleep(0.001)

    ex = GangExecutor(n_lanes=1)
    ex.submit_rt(RTJob("rt", fn, lanes=(0,), prio=5, period_s=0.02))
    try:
        ex.run(0.4)
    finally:
        del junk
    rec = flight.last_run()
    pause = max(rec.gcs, key=lambda g: g.t1 - g.t0)
    assert pause.generation == 2 and pause.t1 - pause.t0 >= 0.01
    late = [t for t in rec.ticks
            if t.late >= 0.5 * (pause.t1 - pause.t0)
            and t.t < pause.t1 and t.t + t.late > pause.t0]
    assert late and late[0].gc_s >= 0.9 * (pause.t1 - pause.t0)
    assert not any(isinstance(cb, flight.GcProbe) for cb in gc.callbacks)


def test_ticks_come_once_per_regulation_interval():
    ex = GangExecutor(n_lanes=1, regulation_interval_s=0.01)
    ex.submit_be(BEJob("be", lambda lane: time.sleep(0.001), lanes=(0,)))
    ex.run(0.3)
    rec = flight.last_run()
    assert rec.tick_s == 0.01
    ts = [t.t for t in rec.ticks]
    # planned at every multiple of the interval; a stall skips some
    assert 5 <= len(ts) <= 30
    assert all(abs(t / 0.01 - round(t / 0.01)) < 1e-6 for t in ts)
    assert all(b > a for a, b in zip(ts, ts[1:]))
    assert all(t.late >= 0 for t in rec.ticks)


def _host_spans(xplane):
    from jax.profiler import ProfileData
    out = {}
    for plane in ProfileData.from_file(xplane).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                t0 = e.start_ns * 1e-9
                out.setdefault(e.name, []).append(
                    (t0, t0 + e.duration_ns * 1e-9))
    return out


def test_anchor_maps_quanta_onto_the_profiler_trace(tmp_path):
    """On the CPU backend, each quantum's recorded [admitted, done]
    mapped through the ``executor.run`` anchor lands on the quantum's
    own ``rt.quantum`` annotation within 1 ms."""
    import jax
    import jax.numpy as jnp

    step = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((128, 128), jnp.float32)
    step(x).block_until_ready()

    def fn(lane, k):
        with jax.profiler.TraceAnnotation("rt.quantum"):
            step(x).block_until_ready()

    ex = GangExecutor(n_lanes=1)
    ex.submit_rt(RTJob("rt", fn, lanes=(0,), prio=5, period_s=0.01))
    jax.profiler.start_trace(str(tmp_path))
    try:
        ex.run(0.4)
    finally:
        jax.profiler.stop_trace()
    rec = flight.last_run()
    spans = _host_spans(glob.glob(os.path.join(
        tmp_path, "**", "*.xplane.pb"), recursive=True)[0])
    (p0, p1), = spans["executor.run"]
    to_prof = rec.to_profiler(p0, p1)
    quanta = sorted(spans["rt.quantum"])
    done = [p for p in rec.releases if p.done == p.done]
    assert len(done) == len(quanta) >= 20
    for p, (q0, q1) in zip(sorted(done, key=lambda p: p.k), quanta):
        assert abs(to_prof(p.admitted) - q0) < 1e-3
        assert abs(to_prof(p.done) - q1) < 1e-3


def test_without_jax_loaded_the_run_has_no_anchor(monkeypatch):
    import sys
    monkeypatch.delitem(sys.modules, "jax.profiler", raising=False)
    ex = GangExecutor(n_lanes=1)
    ex.submit_rt(RTJob("rt", lambda lane, k: None, lanes=(0,), prio=5,
                       period_s=0.01))
    ex.run(0.05)
    rec = flight.last_run()
    assert rec.anchor is None and rec.releases
    with pytest.raises(ValueError):
        rec.to_profiler(0.0, 1.0)
