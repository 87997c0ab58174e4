"""Model step: the model operations of all RT quanta in the window (from
shapes, by the configuration's own count) over their summed wall time at
the chip's bf16 peak (``peaks.json``). In percent."""


def read(run):
    wall = sum(t1 - t0 for _, t0, t1 in run.rt_segments)
    if wall <= 0 or not run.quantum_flops:
        return None
    return 100.0 * sum(run.quantum_flops) / (wall * run.peak["bf16_flops"])
