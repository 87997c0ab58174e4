"""Executor (``rt.pick_lag`` of the flight record): p50 over the window's
releases of the time from the scheduled release to the first of the
gang's lanes taking the gang from the glock's pick: the lane finishing
what it ran when the release fell due, and the release's lazy creation."""
from bench import recorder, stats


def read(run):
    rec = recorder.record()
    if rec is None:
        return None
    first = {}
    for p in recorder.releases(rec, run.window_s):
        if recorder.stamped(p.picked):
            key = (p.job, p.k)
            first[key] = min(first.get(key, p.pick_lag), p.pick_lag)
    return stats.percentile(list(first.values()), 50) * 1e3 if first \
        else None
