"""Arithmetic that more than one per-layer metric reads."""

from bench import flops


def mean_step_ms(run):
    steps = run.window_steps()
    if not steps:
        return None
    return 1e3 * sum(s.t1 - s.t0 for s in steps) / len(steps)


def step_mfu(run):
    steps = run.window_steps()
    wall = sum(s.t1 - s.t0 for s in steps)
    if wall <= 0:
        return None
    m = flops.Model(run.cfg)
    work = sum(flops.step_flops(m, s.entries) for s in steps)
    return 100.0 * work / (wall * run.peaks["bf16_flops_per_s"])
