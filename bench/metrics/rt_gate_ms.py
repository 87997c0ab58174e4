"""Executor (``rt.gate`` of the flight record): p50 over the window's
releases and their lanes of the time from the glock's pick to the
quantum's start: the gang-isolation barrier and admission."""
from bench import recorder, stats


def read(run):
    rec = recorder.record()
    if rec is None:
        return None
    gates = [p.gate for p in recorder.releases(rec, run.window_s)
             if recorder.stamped(p.picked, p.admitted)]
    return stats.percentile(gates, 50) * 1e3 if gates else None
