"""Engine: mean host time of ``ServeEngine.prefill`` in the window (its
first-token read waits for the device)."""


def read(run):
    calls = run.in_window(run.win.calls.prefill)
    return (sum(c[1] - c[0] for c in calls) / len(calls) * 1e3
            if calls else None)
