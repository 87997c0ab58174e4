"""Model step: mean device-busy time per RT quantum, from the profiler
trace: the union of the window's device operations of programs other
than the benchmark's best-effort ones, over the RT quanta in the window."""


def read(run):
    d = run.device
    if d is None or d.rt_spans == 0:
        return None
    return d.rt_device_s / d.rt_spans * 1e3
