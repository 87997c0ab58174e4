"""Regulator (``core/throttle.py``): share of the best-effort lanes' time
in the window in which a lane ran neither an RT nor a best-effort quantum
(stalled by the budget, waiting at the barrier, or idle), from the
executor's trace segments. In percent."""


def read(run):
    lanes = set(run.be_lanes)
    if not lanes:
        return None
    busy = 0.0
    for lane, t0, t1 in run.rt_segments + run.be_segments:
        if lane in lanes:
            busy += max(0.0, min(t1, run.window_s) - max(t0, 0.0))
    return 100.0 * (1.0 - busy / (run.window_s * len(lanes)))
