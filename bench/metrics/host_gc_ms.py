"""Host (``host.gc`` of the flight record): the garbage collector's
pause time summed over the window, every thread of the program stopped
for it."""
from bench import recorder


def read(run):
    rec = recorder.record()
    if rec is None:
        return None
    w = run.window_s
    return 1e3 * sum(max(0.0, min(g.t1, w) - max(g.t0, 0.0))
                     for g in rec.gcs)
