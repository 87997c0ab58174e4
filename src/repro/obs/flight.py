"""The executor's flight recorder (DESIGN.md §12.4): what one
``GangExecutor.run`` leaves behind for a post-mortem, in the style of
ftrace's snapshot buffer.

Every stamp is on the executor's own clock — seconds from the opening
of the run's window, the clock of its ``Trace`` segments and
``response_times``. The record holds:

* ``releases`` — one ``Phase`` per (RT release, gang lane): the
  release's due instant and when ``pick_next_task_rt`` handed the lane
  this gang (``picked``; the last such pick where a preemption sent the
  quantum back to the scheduler), when the quantum was past the
  gang-isolation barrier and admission (``admitted``) and when it
  returned (``done``); NaN where the run ended first. Spans:
  ``rt.pick_lag`` = picked - due, ``rt.gate`` = admitted - picked,
  ``rt.run`` = done - admitted.
* ``windows`` — one ``Window`` per closed regulation window of a lane:
  the quanta the regulator admitted in it and whether it denied any
  (a denial counts once per window, however often the lane retried).
* ``ticks`` — one ``Tick`` per wakeup of the executor's monitor thread
  (``host.tick``): how late it woke against its planned instant, and
  the process CPU time, garbage-collection pause time, involuntary
  context switches and major page faults since the previous tick.
* ``gcs`` — one ``GcPause`` per garbage collection (``host.gc``).
* ``anchor`` — the executor-clock instants at which the run's
  ``executor.run`` profiler annotation opened and closed (None when
  JAX was not loaded): ``to_profiler`` maps any stamp onto a profiler
  trace's clock through them.

Window, tick and GC rows go to rings of ``RING_ROWS`` (``ring()``): a
long run keeps the newest. ``last_run()`` returns the latest finished
run's record.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
from typing import Callable, List, NamedTuple, Optional, Tuple

RING_ROWS = 1 << 16


def ring() -> collections.deque:
    """A bounded ring of rows: appending past ``RING_ROWS`` drops the
    oldest. ``deque.append`` is atomic, so lane threads share one."""
    return collections.deque(maxlen=RING_ROWS)


class Phase(NamedTuple):
    job: str
    k: int                 # release index
    lane: int
    due: float
    picked: float
    admitted: float
    done: float

    @property
    def pick_lag(self) -> float:
        return self.picked - self.due

    @property
    def gate(self) -> float:
        return self.admitted - self.picked

    @property
    def run(self) -> float:
        return self.done - self.admitted


class Window(NamedTuple):
    lane: int
    k: int                 # window index: it spans [k, k+1) intervals
    t_end: float
    used: float
    limit: float
    admitted: int
    denied: bool


class Tick(NamedTuple):
    t: float               # planned instant
    late: float            # woke this long after it
    cpu_s: float           # process CPU time since the previous tick
    gc_s: float            # garbage-collection pause since then
    nivcsw: int            # involuntary context switches since then
    majflt: int            # major page faults since then


class GcPause(NamedTuple):
    generation: int
    t0: float
    t1: float


@dataclasses.dataclass
class FlightRecord:
    window_s: float
    tick_s: float
    releases: List[Phase]
    windows: List[Window]
    ticks: List[Tick]
    gcs: List[GcPause]
    anchor: Optional[Tuple[float, float]] = None
    tick_cpu_s: float = 0.0        # the monitor thread's own CPU time

    def to_profiler(self, p0: float, p1: float) -> Callable[[float], float]:
        """Map an executor-clock stamp onto a profiler trace's clock,
        given the ``executor.run`` span's start ``p0`` and end ``p1``
        there: linear through the anchor, so clock drift over the run
        is taken out too."""
        if self.anchor is None:
            raise ValueError("the run recorded no profiler anchor")
        a0, a1 = self.anchor
        scale = (p1 - p0) / (a1 - a0)
        return lambda t: p0 + (t - a0) * scale


class GcProbe:
    """A ``gc.callbacks`` hook, installed for one run: each collection
    becomes a ``GcPause`` row on the run's clock, and ``paused(now)``
    sums the pause time so far, a collection still open included (the
    monitor thread can win the interpreter lock inside the hook, between
    a collection's end and its row)."""

    def __init__(self, now: Callable[[], float]):
        self._now = now
        # (pause time of the closed collections, start of the open one)
        # as one attribute, so a reader never sees half an update
        self._state: Tuple[float, Optional[float]] = (0.0, None)
        self.rows = ring()

    def __call__(self, phase: str, info: dict) -> None:
        total, t0 = self._state
        if phase == "start":
            self._state = (total, self._now())
        elif t0 is not None:
            t1 = self._now()
            self.rows.append(GcPause(info["generation"], t0, t1))
            self._state = (total + t1 - t0, None)

    def paused(self, now: float) -> float:
        total, t0 = self._state
        return total if t0 is None else total + now - t0

    def __enter__(self) -> "GcProbe":
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self)


_last: Optional[FlightRecord] = None


def publish(record: Optional[FlightRecord]) -> None:
    global _last
    _last = record


def last_run() -> Optional[FlightRecord]:
    """The record of the latest finished ``GangExecutor.run`` in this
    process, or None."""
    return _last


def windows_from_history(history) -> List[Window]:
    """``Window`` rows from a regulator's history ring, whose window
    samples read ``("window", t_end, lane, used, limit, k, admitted,
    denied)``."""
    return [Window(lane=r[2], k=r[5], t_end=r[1], used=r[3], limit=r[4],
                   admitted=r[6], denied=r[7])
            for r in list(history) if r[0] == "window"]
