"""Edat runtime: per request scheduled in the window, the time its
``serve.request`` and ``serve.prefill`` tasks waited in the ready queue
for a worker (each task's start minus its ``ready_ns``, summed), 95th
percentile.  Program spans."""
from .. import spans
from ..stats import percentile


def read(run):
    sp = spans.of(run)
    if sp is None:
        return None
    xs = [sp.start_wait(a) + sp.start_wait(b) for a, b in
          sp.per_request(run.records, "serve.request", "serve.prefill")]
    return percentile(xs, 95) * 1e-6 if xs else None
