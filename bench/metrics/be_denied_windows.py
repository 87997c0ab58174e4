"""Regulator (the flight record's per-window admission rows): the share
of the best-effort lanes' regulation windows closed inside the measured
window in which the regulator denied a charge, a window counted once
however often its lane retried. In percent."""
from bench import recorder


def read(run):
    rec = recorder.record()
    if rec is None:
        return None
    lanes = set(run.be_lanes)
    rows = [w for w in rec.windows
            if w.lane in lanes and w.t_end <= run.window_s]
    if not rows:
        return None
    return 100.0 * sum(1 for w in rows if w.denied) / len(rows)
