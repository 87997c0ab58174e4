"""From a profiler trace (``.xplane.pb``) to device-busy intervals, their
attribution to the benchmark's host spans, the idle gaps, and the
``breakdown`` of the result line.

Device operations are the events of a device plane's ``XLA Ops`` line,
attributed to the program (``XLA Modules`` event) that contains them. A
trace with no device plane (the CPU backend) takes the host events that
carry an ``hlo_module`` stat as its device operations, so the reduction
can be exercised without a chip. Host spans are the
``jax.profiler.TraceAnnotation`` events the benchmark writes
(``bench.window``, ``rt.quantum``, ``be.<kind>``, and the names a
configuration's glue lists in its ``SPANS``, such as ``dave2.forward``).
All times are in seconds on the trace's clock.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import heapq
import os
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

SPANS = ("bench.window", "rt.quantum")
SPAN_PREFIXES = ("be.",)
BE_PROGRAM_PREFIX = "jit_be_"


@dataclasses.dataclass
class Op:
    t0: float
    t1: float
    name: str
    module: str
    device: str


@dataclasses.dataclass
class Span:
    t0: float
    t1: float
    name: str
    thread: str


@dataclasses.dataclass
class Events:
    ops: List[Op]
    spans: List[Span]
    devices: List[str]


def _is_span(name: str, spans: Sequence[str]) -> bool:
    return name in spans or name.startswith(SPAN_PREFIXES)


def _module_name(raw: str) -> str:
    """``jit_dave2_forward(42)`` -> ``jit_dave2_forward``."""
    return raw.split("(")[0].strip()


def _op_name(raw: str) -> str:
    """``%fusion.13 = bf16[64,4096]{...} fusion(...)`` -> ``fusion.13``."""
    return raw.split(" = ")[0].lstrip("%").strip()


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str, glue_spans: Sequence[str] = ()) -> Events:
    import jax
    spans_named = SPANS + tuple(glue_spans)
    pd = jax.profiler.ProfileData.from_file(path)
    ops: List[Op] = []
    spans: List[Span] = []
    devices: List[str] = []
    host_ops: List[Op] = []
    planes = list(pd.planes)
    on_device = any(p.name.startswith("/device:") and "CPU" not in p.name
                    for p in planes)
    for plane in planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" not in lines:
                continue
            devices.append(plane.name)
            mods = sorted((e.start_ns * 1e-9, (e.start_ns + e.duration_ns)
                           * 1e-9, _module_name(e.name))
                          for e in lines["XLA Modules"].events) \
                if "XLA Modules" in lines else []
            starts = [m[0] for m in mods]
            for e in lines["XLA Ops"].events:
                t0 = e.start_ns * 1e-9
                t1 = t0 + e.duration_ns * 1e-9
                i = bisect.bisect_right(starts, t0) - 1
                mod = mods[i][2] if i >= 0 and mods[i][1] >= t0 else ""
                ops.append(Op(t0, t1, _op_name(e.name), mod, plane.name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    t0 = e.start_ns * 1e-9
                    t1 = t0 + e.duration_ns * 1e-9
                    if _is_span(e.name, spans_named):
                        spans.append(Span(t0, t1, e.name, line.name))
                        continue
                    if on_device:
                        continue
                    stats = dict(e.stats)
                    if "hlo_module" in stats and e.duration_ns > 0:
                        host_ops.append(Op(t0, t1, e.name,
                                           str(stats["hlo_module"]),
                                           "host"))
    if not devices:
        ops, devices = host_ops, (["host"] if host_ops else [])
    ops.sort(key=lambda o: o.t0)
    spans.sort(key=lambda s: s.t0)
    return Events(ops=ops, spans=spans, devices=devices)


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def window(ev: Events) -> Tuple[float, float]:
    """The measured window: the harness's ``bench.window`` span."""
    w = [s for s in ev.spans if s.name == "bench.window"]
    if not w:
        raise ValueError("trace holds no bench.window span")
    return w[0].t0, w[0].t1


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                      # per device, averaged over devices
    rt_spans: int
    rt_device_s: float                 # device busy in non-BE programs
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]


def reduce(ev: Events, top: int = 10) -> Reduced:
    lo, hi = window(ev)
    n_dev = max(1, len(ev.devices))
    per_dev: Dict[str, list] = defaultdict(list)
    for o in ev.ops:
        per_dev[o.device].append((o.t0, o.t1))
    busy_dev = {d: union(clip(iv, lo, hi)) for d, iv in per_dev.items()}
    busy = sum(length(iv) for iv in busy_dev.values()) / n_dev

    # device time of the RT job: every program in the window that is not
    # one of the benchmark's best-effort programs runs for an RT quantum.
    # Attributed by program, not by overlap with the host spans: the
    # device's clock in the trace is offset from the host's by tens of
    # microseconds, as long as a DAVE-2 step's device time.
    rt_ops = union(clip([(o.t0, o.t1) for o in ev.ops
                         if not o.module.startswith(BE_PROGRAM_PREFIX)],
                        lo, hi))
    rt_spans = [s for s in ev.spans if s.name == "rt.quantum"
                and s.t1 > lo and s.t0 < hi]
    rt_dev = length(rt_ops) / n_dev

    by_op: Dict[str, float] = defaultdict(float)
    for o, own in self_times(ev.ops, lo, hi):
        by_op[f"{o.module}:{o.name}" if o.module else o.name] += own
    device_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]

    # idle time on the first device, by the host spans open over it
    dev0 = busy_dev.get(ev.devices[0], []) if ev.devices else []
    gaps, t = [], lo
    for a, b in dev0:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    inner = sorted((s for s in ev.spans if s.name != "bench.window"),
                   key=lambda s: s.t0)
    by_host: Dict[str, float] = defaultdict(float)
    for (a, b), who in zip(gaps, _host_activity(inner, gaps)):
        by_host[who] += b - a
    idle = sorted(by_host.items(), key=lambda kv: -kv[1])[:top]
    return Reduced(window_s=hi - lo, busy_s=busy, rt_spans=len(rt_spans),
                   rt_device_s=rt_dev, device_ops=device_ops,
                   idle_gaps=idle)


def self_times(ops: Sequence[Op], lo: float, hi: float):
    """Each operation's own time in [lo, hi]: every instant a device is
    busy goes to the shortest operation running then (a loop's body ops
    sit inside the loop's own event), so no device time counts twice and
    the own times add up to the busy time. Needs no exact nesting: the
    trace's start and end stamps of a body op and its loop may disagree
    by a rounding step."""
    own: Dict[int, float] = defaultdict(float)
    by_dev: Dict[str, List[int]] = defaultdict(list)
    for i, o in enumerate(ops):
        if o.t1 > lo and o.t0 < hi:
            by_dev[o.device].append(i)
    for idx in by_dev.values():
        edges = sorted([(max(ops[i].t0, lo), 1, i) for i in idx] +
                       [(min(ops[i].t1, hi), 0, i) for i in idx])
        active: List[Tuple[float, int]] = []   # heap of (duration, op)
        ended = set()
        t_prev = lo
        for t, starts, i in edges:
            while active and active[0][1] in ended:
                heapq.heappop(active)
            if active and t > t_prev:
                own[active[0][1]] += t - t_prev
            t_prev = t
            if starts:
                heapq.heappush(active, (ops[i].t1 - ops[i].t0, i))
            else:
                ended.add(i)
    return [(ops[i], t) for i, t in own.items() if t > 0]


def _host_activity(spans: List[Span], gaps) -> List[str]:
    """For each gap (in time order), the innermost benchmark span open at
    its midpoint on each host thread, joined; ``host.other`` when none is
    open. One sweep over the spans, sorted by start."""
    out, active, i = [], [], 0
    for a, b in gaps:
        mid = 0.5 * (a + b)
        while i < len(spans) and spans[i].t0 <= mid:
            active.append(spans[i])
            i += 1
        active = [s for s in active if s.t1 > mid]
        inner: Dict[str, Span] = {}
        for s in active:
            cur = inner.get(s.thread)
            if cur is None or s.t1 - s.t0 < cur.t1 - cur.t0:
                inner[s.thread] = s
        names = sorted({s.name for s in inner.values()})
        out.append("+".join(names) if names else "host.other")
    return out


def breakdown(r: Reduced) -> dict:
    return {"device_ops": [[n, s] for n, s in r.device_ops],
            "idle_gaps": [[n, s] for n, s in r.idle_gaps]}
