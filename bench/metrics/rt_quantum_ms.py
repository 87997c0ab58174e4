"""RT quantum (for DAVE-2: the frame's transfer, the forward pass and the
read-back): p50 of the host wall time of the RT quanta, from the
executor's trace segments."""
from bench import stats


def read(run):
    d = [t1 - t0 for _, t0, t1 in run.rt_segments]
    return stats.percentile(d, 50) * 1e3 if d else None
