"""Edat runtime: per request scheduled in the window, the time it waited
for the ``server`` lock, in ``serve.request`` (admission) and in
``serve.attach`` (the splice) together, 0 where it took the lock at
once; 95th percentile.  Program spans."""
from .. import spans
from ..stats import percentile


def read(run):
    sp = spans.of(run)
    if sp is None:
        return None
    xs = [sp.lock_wait(a) + sp.lock_wait(b) for a, b in
          sp.per_request(run.records, "serve.request", "serve.attach")]
    return percentile(xs, 95) * 1e-6 if xs else None
