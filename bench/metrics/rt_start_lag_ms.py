"""Executor layer (glock pick, gang-isolation barrier, admission): p95 over
the window's releases of the time from the scheduled release to the start
of its first RT quantum, from the executor's trace segments."""
from bench import stats


def read(run):
    firsts = {}
    for (lane, t0, t1), k in zip(run.rt_segments, run.rt_release):
        firsts[k] = min(firsts.get(k, t0), t0)
    lags = [t0 - k * run.period_s for k, t0 in firsts.items()]
    return stats.percentile(lags, 95) * 1e3 if lags else None
