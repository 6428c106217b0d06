"""Model step ``jit(prefill_step)``: least time over device time, in
percent, summed over the traced executions.  Least time is the larger of
FLOPs over peak FLOP/s and bytes over HBM bandwidth (``counts/``,
``peaks.json``) for the prompt the execution's host span served."""

PROGRAM = "prefill_step"


def read(run):
    if run.trace is None:
        return None
    calls = run.win.calls.prefill
    least = dev = 0.0
    for idx, ns, name in run.trace["matched"].get("bench.prefill", []):
        if PROGRAM in name:
            least += run.least_s("prefill", calls[idx][2])
            dev += ns * 1e-9
    return 100.0 * least / dev if dev else None
