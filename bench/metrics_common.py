"""Arithmetic that more than one per-layer metric reads."""

from bench import flops, spec


def mean_step_ms(run):
    steps = run.window_steps()
    if not steps:
        return None
    return 1e3 * sum(s.t1 - s.t0 for s in steps) / len(steps)


def step_mfu(run):
    """FLOPs of the window's steps over their wall time times the bf16
    peak of the ``serve.model_axis`` chips a step runs on.  The FLOPs are
    the reference module's ``step_flops(cfg, entries)`` where it has one,
    else ``bench/flops.py``'s dense count."""
    steps = run.window_steps()
    wall = sum(s.t1 - s.t0 for s in steps)
    if wall <= 0:
        return None
    count = getattr(spec.reference(run.cfg), "step_flops", None)
    if count is None:
        m = flops.Model(run.cfg)
        work = sum(flops.step_flops(m, s.entries) for s in steps)
    else:
        work = sum(count(run.cfg, s.entries) for s in steps)
    chips = int(run.cfg["serve"].get("model_axis", 1))
    return 100.0 * work / (wall * chips * run.peaks["bf16_flops_per_s"])


def idle_share(run):
    """Share of the traced part of the window in which no operation ran on
    the chips, busy time averaged over the chips (``bench/trace_reduce``)."""
    tr = run.trace
    if not tr or tr["window_s"] <= 0 or tr["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def hit_token_share(run):
    """Prompt tokens served from cached pages (``PrefixCache.stats
    .hit_tokens`` over the window, summed over the replicas) as a share of
    the prompt tokens of the requests admitted in the window."""
    admitted = sum(n for t, _, n, _ in run.probes
                   if run.t_open <= t < run.t_close)
    return 100.0 * run.cache_hit_tokens / admitted if admitted else None


def beam_attn_roofline(run):
    """The paged beam-attention kernel's share of its roofline over the
    traced part of the window: the least time its calls could take on one
    chip (per call the larger of FLOPs / peak and bytes / bandwidth, from
    the shapes of every replica's steps by ``bench/flops.py``) over the
    device time of its events in the trace, summed over the chips.  None
    without kernel events."""
    tr = run.trace
    if not tr or tr["kernel_s"] <= 0:
        return None
    m = flops.Model(run.cfg)
    steps = [s.entries for s in run.steps
             if tr["t_open_host"] <= s.t0 and s.t1 <= run.t_close]
    least = flops.least_time(flops.beam_attn_calls(m, steps),
                             run.peaks["bf16_flops_per_s"],
                             run.peaks["hbm_bytes_per_s"])
    kernel_s = tr["kernel_s"] * tr["devices"]
    return 100.0 * least / kernel_s if least > 0 else None
