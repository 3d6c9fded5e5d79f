"""Model FLOP/s utilization of the traced training window: the forward and
backward FLOPs the steps require (``bench.flops``, recompute not counted)
over the window times the chip's peak bf16 FLOP/s, in %."""


def read(run):
    c = run.counts
    if not c.get("steps") or not run.trace.window_s:
        return None
    flops = c["model_flops_per_step"] * c["steps"]
    return 100.0 * flops / (run.trace.window_s * run.peaks["bf16_flops_per_s"])
