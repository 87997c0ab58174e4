"""Observability layer (src/repro/obs/, DESIGN.md §12): metrics
registry semantics, the engine-parity contract on the fig4/fig5
workloads, RTA-margin accounting, timeline agreement between the two
engines, and the Perfetto export round-trip."""
import json

import pytest

from repro.core.gang import BETask, RTTask
from repro.core.sim import Simulator, matrix_interference
from repro.core.tracing import Trace
from repro.obs.margins import margin_summary, merge_margins, overall
from repro.obs.metrics import MetricsRegistry, series_key
from repro.obs.perfetto import (export_sim, export_trace,
                                segments_from_json, validate_chrome_trace)

DT = 0.05


def fig4_taskset():
    t1 = RTTask("tau1", wcet=2, period=10, cores=(0, 1), prio=2,
                mem_budget=1e9)
    t2 = RTTask("tau2", wcet=4, period=10, cores=(2, 3), prio=1,
                mem_budget=1e9)
    be = [BETask("tau3", cores=(0, 1, 2, 3))]
    return [t1, t2], be, None


def fig5_taskset():
    t1 = RTTask("tau1", wcet=3.5, period=20, cores=(0, 1), prio=2,
                mem_budget=0.1)
    t2 = RTTask("tau2", wcet=6.5, period=30, cores=(2, 3), prio=1,
                mem_budget=0.1)
    bem = BETask("be_mem", cores=(0, 1, 2, 3), mem_rate=1.0)
    bec = BETask("be_cpu", cores=(0, 1, 2, 3), mem_rate=0.01)
    intf = matrix_interference({
        ("tau1", "tau2"): 2.0, ("tau2", "tau1"): 2.0,
        ("tau1", "be_mem"): 1.5, ("tau2", "be_mem"): 1.5,
    })
    return [t1, t2], [bem, bec], intf


def run(taskset, dt, horizon=120.0, **kw):
    rts, bes, intf = taskset()
    if intf is not None:
        kw["interference"] = intf
    sim = Simulator(4, rts, be_tasks=bes, rt_gang_enabled=True, dt=dt,
                    throttle_mode="reactive", **kw)
    return sim, sim.run(horizon)


# ---------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------

def test_registry_get_or_create_shares_instruments():
    reg = MetricsRegistry()
    a = reg.counter("x", gang="g0")
    b = reg.counter("x", gang="g0")
    c = reg.counter("x", gang="g1")
    assert a is b and a is not c
    a.value += 3
    assert reg.snapshot() == {"x{gang=g0}": 3, "x{gang=g1}": 0}


def test_series_key_sorts_labels():
    assert series_key("n", {}) == "n"
    assert series_key("n", {"b": 2, "a": 1}) == "n{a=1,b=2}"


def test_common_labels_fold_into_every_series():
    reg = MetricsRegistry(common_labels={"policy": "rtgT"})
    reg.counter("trips", core=0).value += 1
    assert reg.snapshot() == {"trips{core=0,policy=rtgT}": 1}


def test_disabled_registry_hands_out_working_detached_instruments():
    reg = MetricsRegistry(enabled=False)
    a = reg.counter("x")
    b = reg.counter("x")
    assert a is not b           # nothing is indexed or shared
    a.inc(2)
    assert a.value == 2         # the caller's accounting still works
    assert reg.snapshot() == {}
    assert reg.parity_snapshot() == {}


def test_histogram_buckets_count_and_summary():
    reg = MetricsRegistry()
    h = reg.histogram("lat", bounds=(1.0, 10.0))
    for v in (0.5, 5.0, 50.0, 0.7):
        h.observe(v)
    s = h.summary()
    assert s["count"] == 4
    assert s["min"] == 0.5 and s["max"] == 50.0
    assert s["buckets"] == {"1.0": 2, "10.0": 1, "+inf": 1}


def test_parity_snapshot_rejects_non_integer():
    reg = MetricsRegistry()
    reg.counter("bad", parity=True).value = 1.5
    with pytest.raises(ValueError):
        reg.parity_snapshot()


# ---------------------------------------------------------------------
# margins
# ---------------------------------------------------------------------

def test_margin_summary_flags_negative_margins():
    resp = {"a": [1.0, 2.0, 4.5], "b": []}
    out = margin_summary(resp, {"a": 4.0, "b": 7.0})
    assert out["a"]["jobs"] == 3
    assert out["a"]["worst_margin"] == pytest.approx(-0.5)
    assert out["a"]["negative"] == 1
    assert out["b"] == {"bound": 7.0, "jobs": 0, "worst_margin": None,
                        "mean_margin": None, "negative": 0}


def test_merge_margins_pools_jobs_and_mins_worst():
    a = margin_summary({"t": [1.0]}, {"t": 5.0})
    b = margin_summary({"t": [3.0, 4.0]}, {"t": 5.0})
    merged = merge_margins(dict(a), b)
    assert merged["t"]["jobs"] == 3
    assert merged["t"]["worst_margin"] == pytest.approx(1.0)
    assert merged["t"]["mean_margin"] == pytest.approx((4 + 2 + 1) / 3)
    assert overall(merged) == {
        "tasks": 1, "jobs": 3,
        "worst_margin": pytest.approx(1.0), "negative": 0}


def test_sim_result_carries_margins_and_metrics():
    reg = MetricsRegistry()
    _, r = run(fig5_taskset, None, metrics=reg,
               rta_bounds={"tau1": 5.25, "tau2": 15.0})
    assert r.rta_margins["tau1"]["jobs"] > 0
    assert r.rta_margins["tau1"]["negative"] == 0
    assert r.rta_margins["tau2"]["negative"] == 0
    assert r.metrics is not None and r.parity_metrics is not None
    # the histogram flowed into the shared registry too
    assert "rta.margin{gang=tau1}" in r.metrics
    assert r.parity_metrics["glock.acquisitions"] > 0


# ---------------------------------------------------------------------
# engine parity: byte-identical parity counters on fig4/fig5
# ---------------------------------------------------------------------

@pytest.mark.parametrize("taskset", [fig4_taskset, fig5_taskset],
                         ids=["fig4", "fig5"])
def test_engine_parity_metrics(taskset):
    regs = {}
    snaps = {}
    for engine, dt in (("quantum", DT), ("event", None)):
        regs[engine] = MetricsRegistry()
        _, r = run(taskset, dt, metrics=regs[engine])
        snaps[engine] = r.parity_metrics
    assert snaps["quantum"] == snaps["event"]
    # byte-identical, not merely equal-as-dicts
    assert json.dumps(snaps["quantum"], sort_keys=True) == \
        json.dumps(snaps["event"], sort_keys=True)
    # and non-vacuous: the scheduler and task series actually counted
    s = snaps["event"]
    assert s["glock.acquisitions"] > 0
    assert s["task.completions{gang=tau1}"] > 0
    assert any(k.startswith("task.releases") for k in s)


def test_parity_includes_fault_counters():
    from repro.core.faults import Enforcement, FaultPlan, WcetOverrun
    plan = FaultPlan(faults=(WcetOverrun("tau2", factor=3.0, prob=1.0),),
                     seed=7)
    enf = Enforcement(action="abort", factor=1.2)
    snaps = {}
    for engine, dt in (("quantum", DT), ("event", None)):
        reg = MetricsRegistry()
        rts, bes, intf = fig5_taskset()
        sim = Simulator(4, rts, be_tasks=bes, interference=intf,
                        rt_gang_enabled=True, dt=dt, fault_plan=plan,
                        enforcement=enf, metrics=reg)
        snaps[engine] = sim.run(120.0).parity_metrics
    assert snaps["quantum"] == snaps["event"]
    assert snaps["event"]["faults.injected{kind=overrun}"] > 0
    assert snaps["event"]["faults.enforced{action=abort}"] > 0


# ---------------------------------------------------------------------
# timeline agreement: Trace.intervals across engines on fig5
# ---------------------------------------------------------------------

def test_intervals_agree_across_engines_fig5():
    # the quantum engine emits dt-sized touching segments, the event
    # engine long exact ones; merged per-task intervals must agree to
    # within the quantum discretization envelope
    _, q = run(fig5_taskset, 0.025)
    _, e = run(fig5_taskset, None)
    for name in ("tau1", "tau2"):
        qi = q.trace.intervals(name, tol=0.026)
        ei = e.trace.intervals(name)
        assert len(qi) == len(ei), name
        for (q0, q1), (e0, e1) in zip(qi, ei):
            assert q0 == pytest.approx(e0, abs=0.06)
            assert q1 == pytest.approx(e1, abs=0.06)


# ---------------------------------------------------------------------
# Perfetto export
# ---------------------------------------------------------------------

def test_perfetto_roundtrip_exact():
    sim, r = run(fig5_taskset, None, record_counters=True)
    data = export_sim(sim, r, title="fig5")
    assert validate_chrome_trace(data) == []
    # through an actual JSON serialization, as a viewer would read it
    parsed = json.loads(json.dumps(data))
    got = segments_from_json(parsed)
    want = sorted(((s.core, s.label, s.t0, s.t1)
                   for s in r.trace.segments if s.label is not None),
                  key=lambda t: (t[0], t[2]))
    assert got == want


def test_perfetto_roundtrip_with_an_executor_flight_record():
    """An executor run exported with its flight record: the core tracks
    still invert exactly, and the phase, host and per-window tracks
    carry the record's rows."""
    import time
    from repro.core.executor import BEJob, GangExecutor, RTJob
    from repro.obs import flight
    from repro.obs.perfetto import PID_HOST, PID_PHASES

    ex = GangExecutor(n_lanes=2, regulation_interval_s=0.005)
    ex.submit_rt(RTJob("rt", lambda lane, k: time.sleep(0.001), lanes=(0,),
                       prio=5, period_s=0.01, budget_bytes=2.0))
    ex.submit_be(BEJob("be", lambda lane: time.sleep(0.002), lanes=(0, 1),
                       bytes_per_quantum=1.0))
    ex.run(0.3)
    rec = flight.last_run()
    data = json.loads(json.dumps(export_trace(
        ex.trace, rt_names=["rt"], title="executor", flight=rec)))
    assert validate_chrome_trace(data) == []
    want = sorted(((s.core, s.label, s.t0, s.t1)
                   for s in ex.trace.segments if s.label is not None),
                  key=lambda t: (t[0], t[2]))
    assert segments_from_json(data) == want
    evs = data["traceEvents"]
    phases = [e for e in evs if e["ph"] == "X" and e["pid"] == PID_PHASES]
    picked = [p for p in rec.releases if p.picked == p.picked]
    assert sum(e["name"] == "rt.pick_lag" for e in phases) == len(picked)
    gate = next(e for e in phases if e["name"] == "rt.gate")
    p = next(p for p in picked if p.k == gate["args"]["k"])
    assert gate["ts"] == pytest.approx(p.picked * 1e6)
    assert gate["dur"] == pytest.approx(p.gate * 1e6)
    host = [e for e in evs if e["ph"] == "X" and e["pid"] == PID_HOST]
    assert sum(e["name"] == "host.gc" for e in host) == len(rec.gcs)
    windows = [e for e in evs if e["ph"] == "C"
               and e["name"] == "be window lane 1"]
    assert len(windows) == sum(w.lane == 1 for w in rec.windows) > 0
    assert set(windows[0]["args"]) == {"admitted", "denied"}


def test_perfetto_span_classification_and_counter_tracks():
    sim, r = run(fig5_taskset, None, record_counters=True)
    data = export_sim(sim, r, title="fig5")
    evs = data["traceEvents"]
    cats = {e["cat"] for e in evs if e["ph"] == "X"}
    assert "gang" in cats and "be" in cats
    # fig5's regulator stalls BE cores: throttled spans colored apart
    assert "throttle" in cats
    counters = {e["name"] for e in evs if e["ph"] == "C"}
    assert any(c.startswith("bw core") for c in counters)
    assert "glock held" in counters
    # per-core thread metadata for the viewer
    names = {e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert names == {f"core {c}" for c in range(4)}


def test_validate_chrome_trace_rejects_malformed():
    assert validate_chrome_trace([]) != []
    assert validate_chrome_trace({"traceEvents": [{"ph": "Z"}]}) != []
    bad_counter = {"traceEvents": [
        {"ph": "C", "pid": 2, "tid": 0, "name": "c", "ts": 1.0,
         "args": {"v": "high"}}]}
    assert validate_chrome_trace(bad_counter) != []


def test_export_trace_skips_idle_and_classifies_pathology():
    tr = Trace(2)
    tr.record(0, "g0", 0.0, 1.0)
    tr.record(0, None, 1.0, 2.0)
    tr.record(1, "throttled:be", 0.0, 0.5)
    tr.record(1, "dem:g1", 0.5, 1.0)
    tr.record(1, "aborted:g1#3", 1.0, 1.5)
    data = export_trace(tr, rt_names=["g0"])
    xs = [e for e in data["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"g0", "throttled:be", "dem:g1",
                                       "aborted:g1#3"}
    by_name = {e["name"]: e["cat"] for e in xs}
    assert by_name == {"g0": "gang", "throttled:be": "throttle",
                       "dem:g1": "dem", "aborted:g1#3": "aborted"}


# ---------------------------------------------------------------------
# tracing satellites: CSV round-trip, zero-span render
# ---------------------------------------------------------------------

def test_trace_csv_roundtrip_with_pathological_labels():
    tr = Trace(2)
    tr.record(0, "tau1", 0.0, 2.5)
    tr.record(0, None, 2.5, 3.0)            # idle -> empty field
    tr.record(1, "throttled:be_mem", 0.0, 1.0)
    tr.record(1, 'odd,"label"', 1.0, 2.0)   # quoting stress
    text = tr.to_csv()
    back = Trace.from_csv(text)
    assert back.n_cores == 2
    assert [(s.core, s.label, s.t0, s.t1) for s in back.segments] == \
        [(s.core, s.label, s.t0, s.t1) for s in tr.segments]


def test_render_ascii_zero_span_does_not_divide():
    tr = Trace(1)
    tr.record(0, "t", 5.0, 5.1)
    out = tr.render_ascii(t_start=5.0, t_end=5.0)
    assert "core0" in out       # renders the instant instead of raising
