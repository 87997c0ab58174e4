"""The program's flight record of the run just measured
(``repro.obs.flight.last_run()``), for the per-layer metrics that read
it. Stamps are seconds from the opening of the window, as in
``RunView``. ``record()`` is None where the program keeps no such
record, so its readers then report nothing."""
from __future__ import annotations

import math


def record():
    try:
        from repro.obs.flight import last_run
    except ImportError:
        return None
    return last_run()


def releases(rec, window_s: float):
    """The phase rows of the releases due inside the window."""
    return [p for p in rec.releases if p.due < window_s]


def stamped(*xs: float) -> bool:
    """Whether every stamp was taken: NaN marks a phase the run ended
    before."""
    return not any(math.isnan(x) for x in xs)
