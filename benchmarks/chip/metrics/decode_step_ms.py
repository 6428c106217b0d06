"""Engine: mean host time of ``ServeEngine.step`` in the window (its
token read-back waits for the device)."""


def read(run):
    calls = run.in_window(run.win.calls.step)
    return (sum(c[1] - c[0] for c in calls) / len(calls) * 1e3
            if calls else None)
