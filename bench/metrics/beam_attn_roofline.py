"""Kernels: the paged beam-attention kernel's share of its roofline over
the traced part of the window: the least time its calls could take (per
call the larger of FLOPs / peak and bytes / bandwidth, from the steps'
shapes by ``bench/flops.py``) over the device time of its events in the
trace.  Nothing to read without kernel events."""

from bench import flops


def read(run):
    tr = run.trace
    if not tr or tr["kernel_s"] <= 0:
        return None
    m = flops.Model(run.cfg)
    steps = [s.entries for s in run.steps
             if tr["t_open_host"] <= s.t0 and s.t1 <= run.t_close]
    least = flops.least_time(flops.beam_attn_calls(m, steps),
                             run.peaks["bf16_flops_per_s"],
                             run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / tr["kernel_s"] if least > 0 else None
