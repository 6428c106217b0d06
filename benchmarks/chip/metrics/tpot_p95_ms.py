"""Time per output token after the first, ``(t_done - t_first) / (n_out -
1)`` per request, 95th percentile over requests with more than one."""
from ..stats import percentile


def read(run):
    xs = [(r["t_done"] - r["t_first"]) / (r["n_out"] - 1)
          for r in run.records if r["n_out"] > 1]
    return percentile(xs, 95) * 1e3 if xs else None
