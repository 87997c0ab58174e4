"""Executor best-effort filler: p50 of the host wall time of the
best-effort quanta, from the executor's trace segments."""
from bench import stats


def read(run):
    d = [t1 - t0 for _, t0, t1 in run.be_segments]
    return stats.percentile(d, 50) * 1e3 if d else None
