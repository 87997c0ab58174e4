"""Device (TPU): one minus the union of device-operation intervals over
the traced window, from the profiler trace. In percent."""


def read(run):
    d = run.device
    if d is None or d.window_s <= 0:
        return None
    return 100.0 * (1.0 - d.busy_s / d.window_s)
