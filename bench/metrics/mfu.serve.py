"""Model FLOP/s utilization of the traced serving window, in %: 2 FLOPs per
matmul weight for every prompt token prefilled and every token generated
in it, over the window times the chip's peak bf16 FLOP/s."""


def read(run):
    c = run.counts
    tokens = c.get("prefilled_tokens", 0) + c.get("generated_tokens", 0)
    if not tokens or not run.trace.window_s:
        return None
    flops = 2 * c["matmul_params"] * tokens
    return 100.0 * flops / (run.trace.window_s * run.peaks["bf16_flops_per_s"])
