"""Serve program: time from scheduled arrival to admission (the prefill
task starting), ``t_admit - t_sched``, 95th percentile."""
from ..stats import percentile


def read(run):
    xs = [r["t_admit"] - r["t_sched"] for r in run.records]
    return percentile(xs, 95) * 1e3 if xs else None
