"""Host (``host.tick`` of the flight record): the latest the executor's
monitor thread woke against its planned instant in the window. A tick
wakes once per regulation interval, so a host that stands still shows
here with the length of its stall."""
from bench import recorder


def read(run):
    rec = recorder.record()
    if rec is None:
        return None
    late = [t.late for t in rec.ticks if t.t < run.window_s]
    return max(late) * 1e3 if late else None
