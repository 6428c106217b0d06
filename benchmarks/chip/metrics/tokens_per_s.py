"""Output tokens produced inside the window over its length: one for each
prefill and one for each live slot of each decode step that returned in
the window."""


def read(run):
    t0, t1 = run.win.t0, run.win.t1
    n = sum(1 for c in run.win.calls.prefill if t0 <= c[1] <= t1)
    n += sum(len(c[2]) for c in run.win.calls.step if t0 <= c[1] <= t1)
    return n / run.win.seconds if n else None
