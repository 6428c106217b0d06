"""Whole decode step: model FLOPs of the live slots' tokens over the summed
step-to-step host intervals (start to next start, while a slot stays
live) times the chip's peak FLOP/s, in percent."""


def read(run):
    pairs = run.tick_intervals()
    secs = sum(b[0] - a[0] for a, b in pairs)
    if not secs:
        return None
    flops = sum(run.counts("decode", a[3])[0] for a, _ in pairs)
    return 100.0 * flops / (secs * run.peak["flops_bf16"])
