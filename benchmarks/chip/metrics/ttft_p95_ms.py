"""Time to first token, from each request's scheduled arrival (open loop),
95th percentile over every request scheduled in the window."""
from ..stats import percentile


def read(run):
    xs = [r["t_first"] - r["t_sched"] for r in run.records]
    return percentile(xs, 95) * 1e3 if xs else None
