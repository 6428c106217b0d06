"""Edat runtime (the ``decode_tick`` chain): mean host time from one
``ServeEngine.step`` return to the next call while a slot stays live."""


def read(run):
    gaps = [b[0] - a[1] for a, b in run.tick_intervals()]
    return sum(gaps) / len(gaps) * 1e3 if gaps else None
