"""Runs one cell: finds its files by name, builds it, warms its shapes,
drives ``GangExecutor.run`` for the window, reduces what it recorded to
the cell's metrics, and checks what the timed path produced.

The end-to-end metrics (``--trace 0``) come from the host clock over the
whole window; the per-layer metrics (``--trace 1``) from the executor's
trace segments and the profiler's trace of the same window.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import time
from typing import Callable, Dict, List, Optional, Tuple

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
RT, BE = "rt", "be"
# A best-effort quantum is charged a few microseconds before the executor
# stamps its start; a start this far into a regulation window is certain
# to have been charged to that window (the budget check counts only these).
CHARGE_SLACK_S = 0.002


class NoChip(RuntimeError):
    pass


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class CellFiles:
    workload: dict
    spec: dict
    mix: dict
    glue: str
    be: str


def resolve(bm: dict, workload: str, bench: str = BENCH) -> CellFiles:
    """The files a cell is made of, found by the names in BENCHMARK.json."""
    cells = {w["name"]: w for w in bm["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{sorted(cells)}")
    w = cells[workload]
    cfg = {c["name"]: c for c in bm["configs"]}[w["config"]]
    spec = load_json(os.path.join(os.path.dirname(bench), cfg["file"]))
    spec["name"] = w["config"]
    mix = load_json(os.path.join(bench, "traffic", w["traffic"] + ".json"))
    return CellFiles(
        workload=w, spec=spec, mix=mix,
        glue=os.path.join(bench, "configs", w["config"] + ".py"),
        be=os.path.join(bench, "be", mix["be"] + ".py"))


def devices(require_tpu: bool, chips: int) -> Tuple[list, dict]:
    import jax
    devs = jax.devices()
    d = devs[0]
    if require_tpu and d.platform != "tpu":
        raise NoChip(f"no TPU: JAX reports {len(devs)} {d.platform} "
                     f"device(s) ({d.device_kind})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX reports "
                     f"{len(devs)}")
    return devs, {"platform": d.platform, "kind": d.device_kind,
                  "count": len(devs)}


def enable_cache():
    """The program's compile cache directory (fixed, inside the checkout,
    or ``$JAX_COMPILATION_CACHE_DIR``), keeping every program, so only a
    checkout's first run compiles."""
    import jax
    from repro.launch.cache import enable_compile_cache
    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return where


@dataclasses.dataclass
class RunView:
    """What the per-layer metric readers see of one run; times in seconds
    from the opening of the window."""
    window_s: float
    period_s: float
    rt_segments: List[Tuple[int, float, float]]
    rt_release: List[int]
    be_segments: List[Tuple[int, float, float]]
    be_lanes: Tuple[int, ...]
    quantum_flops: List[float]
    peak: Optional[dict]
    responses: List[float] = dataclasses.field(default_factory=list)
    device: Optional[object] = None


def segments(trace, label: str):
    return sorted(((s.core, s.t0 * 1e-3, s.t1 * 1e-3)
                   for s in trace.segments if s.label == label),
                  key=lambda x: x[1])


def release_index(rt_segments) -> List[int]:
    """Each lane runs one quantum per release, in release order."""
    seen: Dict[int, int] = {}
    out = []
    for lane, _, _ in rt_segments:
        out.append(seen.get(lane, 0))
        seen[lane] = out[-1] + 1
    return out


def end_to_end(period: float, window: float, finished: List[float],
               be_segments) -> Tuple[dict, int, int]:
    from bench import stats
    resp = stats.release_responses(period, window, finished)
    dl = stats.deadline_met(period, window, finished)
    be_done = sum(1 for _, _, t1 in be_segments if t1 <= window)
    metrics = {
        "rt_resp_p50_ms": stats.percentile(resp, 50) * 1e3,
        "rt_deadline_met": dl["share"],
        "be_quanta_per_s": stats.rate(be_done, window),
    }
    return metrics, len(resp), dl["due"] - dl["met"]


def scheduler_checks(view: RunView, finished: List[float],
                     budget: float, be_bytes: float,
                     interval: float) -> Dict[str, tuple]:
    """The scheduler's guarantees, as far as the trace shows them: one
    gang at a time, best-effort work within its budget in every
    regulation window, and every release at its scheduled instant."""
    rt = view.rt_segments
    overlap = 0
    by_rel: Dict[int, list] = {}
    for seg, k in zip(rt, view.rt_release):
        by_rel.setdefault(k, []).append(seg)
    spans = sorted((min(s[1] for s in v), max(s[2] for s in v), k)
                   for k, v in by_rel.items())
    for (a0, a1, ka), (b0, b1, kb) in zip(spans, spans[1:]):
        if b0 < a1 - 1e-9 and ka != kb:
            overlap += 1
    drift = 0.0
    for k, resp in enumerate(finished):
        if k in by_rel:
            fin = max(s[2] for s in by_rel[k])
            drift = max(drift, abs(fin - resp - k * view.period_s))
    over = 0
    if be_bytes > 0 and rt and math.isfinite(budget):
        allowed = math.floor(budget / be_bytes + 1e-9)
        first = int(rt[0][1] // interval) + 1
        counts: Dict[Tuple[int, int], int] = {}
        for lane, t0, _ in view.be_segments:
            k = int(t0 // interval)
            if k >= first and t0 - k * interval >= CHARGE_SLACK_S:
                counts[(lane, k)] = counts.get((lane, k), 0) + 1
        over = sum(1 for n in counts.values() if n > allowed)
    return {
        "gang_overlap": (overlap, 0, "<="),
        "be_over_budget_windows": (over, 0, "<="),
        "release_drift_ms": (drift * 1e3, 0.001, "<="),
    }


def passes(value, limit, op) -> bool:
    if value is None or (isinstance(value, float) and math.isnan(value)):
        return False
    return value <= limit if op == "<=" else value >= limit


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             bm: Optional[dict] = None, bench: str = BENCH,
             require_tpu: bool = True, cache: bool = True,
             small: bool = False, fault: Optional[str] = None,
             peak: Optional[dict] = None, control: bool = False,
             t_start: Optional[float] = None,
             log: Callable[[str], None] = lambda m: None) -> dict:
    """One run of one cell; returns the result line as a dict, with the
    numbers compared under ``checks`` (last). With ``control`` the
    control's outputs take the program's place in the comparison, and
    the program's own reading goes under ``program`` (``bench/control.py``);
    ``small``, ``fault``, ``peak`` and ``require_tpu=False`` are for the
    CPU tests only."""
    t_start = time.monotonic() if t_start is None else t_start
    bm = bm if bm is not None else load_json(os.path.join(ROOT,
                                                          "BENCHMARK.json"))
    files = resolve(bm, workload, bench)
    devs, device = devices(require_tpu, files.workload["chips"])
    log(f"set-up: backend ready at {time.monotonic() - t_start:.2f} s")
    if peak is None:
        peaks = load_json(os.path.join(bench, "peaks.json"))
        if device["kind"] not in peaks:
            raise KeyError(f"no peaks for device kind {device['kind']!r} "
                           f"in peaks.json")
        peak = peaks[device["kind"]]
    import jax
    if cache:
        log(f"compile cache: {enable_cache()}")
    from repro.core.executor import BEJob, GangExecutor, RTJob

    glue = load_module(files.glue, f"bench_cfg_{files.spec['name']}")
    be_mod = load_module(files.be, f"bench_be_{files.mix['be']}")
    cell = glue.build(files.spec, files.mix, seed, seconds, small=small,
                      fault=None if fault == "budget" else fault)
    be_fn, be_bytes = be_mod.make(seed, small)
    log(f"set-up: cell and co-runner built at "
        f"{time.monotonic() - t_start:.2f} s")
    cell.warm()
    for lane in cell.be_lanes:
        be_fn(lane)
    log(f"set-up: warm at {time.monotonic() - t_start:.2f} s")
    budget = float("inf") if fault == "budget" else cell.be_budget_bytes

    ex = GangExecutor(n_lanes=cell.n_lanes,
                      regulation_interval_s=cell.regulation_s)
    ex.submit_rt(RTJob(name=RT, fn=cell.quantum, lanes=cell.rt_lanes,
                       prio=cell.rt_prio, period_s=cell.period_s,
                       budget_bytes=budget))
    ex.submit_be(BEJob(name=BE, fn=be_fn, lanes=cell.be_lanes,
                       bytes_per_quantum=be_bytes))

    compiles = []
    in_window = [False]

    def on_event(event, duration, **kw):
        if in_window[0] and event.endswith("backend_compile_duration"):
            compiles.append(duration)
    jax.monitoring.register_event_duration_secs_listener(on_event)

    trace_dir = os.path.join(bench, ".trace")
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        # host spans and device operations only: the Python tracer would
        # put a probe on every Python call of the quanta it measures
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    setup_s = time.monotonic() - t_start
    in_window[0] = True
    with jax.profiler.TraceAnnotation("bench.window"):
        ex.run(seconds)
    in_window[0] = False
    if trace:
        jax.profiler.stop_trace()
    mem = devs[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(mem.get("peak_bytes_in_use", 0))
    log(f"compiles inside the window: {len(compiles)}")

    finished = list(ex.response_times[RT])
    rt_segs = segments(ex.trace, RT)
    be_segs = segments(ex.trace, BE)
    view = RunView(window_s=seconds, period_s=cell.period_s,
                   rt_segments=rt_segs, rt_release=release_index(rt_segs),
                   be_segments=be_segs, be_lanes=cell.be_lanes,
                   quantum_flops=list(cell.quantum_flops), peak=peak)
    e2e, attempted, failed = end_to_end(cell.period_s, seconds, finished,
                                        be_segs)
    from bench import stats
    view.responses = stats.release_responses(cell.period_s, seconds,
                                             finished)
    result: dict = {"correct": False, "attempted": attempted,
                    "failed": failed}
    units = {m["name"]: m["unit"] for m in bm["end_to_end"] + bm["per_layer"]}
    if trace:
        from bench import trace_reduce
        ev = trace_reduce.load(trace_reduce.find_xplane(trace_dir),
                                getattr(glue, "SPANS", ()))
        view.device = trace_reduce.reduce(ev)
        shutil.rmtree(trace_dir, ignore_errors=True)
        metrics = {}
        for m in bm["per_layer"]:
            if workload not in m.get("workloads", [workload]):
                continue
            reader = load_module(os.path.join(bench, "metrics",
                                              m["name"] + ".py"),
                                 f"bench_metric_{m['name']}")
            v = reader.read(view)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": units[m["name"]]}
        device["busy_s"] = view.device.busy_s
        device["window_s"] = view.device.window_s
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = trace_reduce.breakdown(view.device)
    else:
        e2e["setup_s"] = setup_s
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in bm["end_to_end"]
                             if workload in m.get("workloads", [workload])}
        result["device"] = device

    checks = scheduler_checks(view, finished, cell.be_budget_bytes,
                              be_bytes, cell.regulation_s)
    # the reference runs on the chip's memory alone: nothing of the
    # program's state, the executor or the co-runner may stay alive
    outputs = cell.finish()
    del ex, be_fn, be_mod
    gc.collect()
    log(f"device bytes alive before the reference: "
        f"{sum(a.nbytes for a in jax.live_arrays())}")
    t_ref = time.monotonic()
    limits = files.spec["check"]
    checks.update(cell.check(outputs, limits))
    if control:
        # the program's own reading first, then the control in its place,
        # through the same comparison: `correct` is then the control's
        result["program"] = {"correct": all(passes(*c)
                                            for c in checks.values()),
                             "checks": _shown(checks)}
        checks.update(cell.check(outputs, limits, control=True))
    log(f"reference and checks: {time.monotonic() - t_ref:.1f} s")
    result["correct"] = all(passes(*c) for c in checks.values())
    result["checks"] = _shown(checks)
    return result


def _shown(checks: Dict[str, tuple]) -> dict:
    return {k: {"value": v, "limit": lim, "op": op}
            for k, (v, lim, op) in checks.items()}
