"""Share of its roofline a decode step reaches, in %: the bytes a step
needs, the served weights in their at-rest format (``bench.flops.
weight_bytes_at_rest``) plus the live cache entries of every request in
flight, over peak HBM bandwidth, divided by the device time per step of the
decode module.  At this batch a decode step is bound by bandwidth."""

MODULE = "jit__decode"


def read(run):
    m = run.trace.modules.get(MODULE)
    c = run.counts
    if not m or not m[0] or not m[1] or "kv_entries_per_tick" not in c:
        return None
    step_bytes = c["weight_bytes"] + c["kv_entries_per_tick"] * c["kv_bytes_per_entry"]
    floor_s = step_bytes / run.peaks["hbm_bytes_per_s"]
    per_step_s = m[1] / m[0] / c["drain_every"]
    return 100.0 * floor_s / per_step_s
