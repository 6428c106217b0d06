"""Model step ``jit(serve_step)``: least time over device time, in
percent, summed over the traced executions.  Least time is the larger of
FLOPs over peak FLOP/s and bytes over HBM bandwidth (``counts/``,
``peaks.json``) for the live slots of the step the host span issued:
bf16 weights once, live slots' cache read and new entries written."""

PROGRAM = "serve_step"


def read(run):
    if run.trace is None:
        return None
    calls = run.win.calls.step
    least = dev = 0.0
    for idx, ns, name in run.trace["matched"].get("bench.step", []):
        if PROGRAM in name and calls[idx][3]:
            least += run.least_s("decode", calls[idx][3])
            dev += ns * 1e-9
    return 100.0 * least / dev if dev else None
