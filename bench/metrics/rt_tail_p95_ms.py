"""The RT gang's response tail: the 95th percentile of (gang finish -
scheduled release) over every release due in the window, a release
unfinished at the close counted with its wait so far (host clock). A
host that stands still for a second puts it in the hundreds of
milliseconds, so it is no end-to-end metric here."""
from bench import stats


def read(run):
    return stats.percentile(run.responses, 95) * 1e3 if run.responses \
        else None
