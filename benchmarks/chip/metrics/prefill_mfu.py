"""Whole prefill: model FLOPs of the prompts prefilled in the window over
their summed host time times the chip's peak FLOP/s, in percent."""


def read(run):
    calls = run.in_window(run.win.calls.prefill)
    secs = sum(c[1] - c[0] for c in calls)
    if not secs:
        return None
    flops = sum(run.counts("prefill", c[2])[0] for c in calls)
    return 100.0 * flops / (secs * run.peak["flops_bf16"])
