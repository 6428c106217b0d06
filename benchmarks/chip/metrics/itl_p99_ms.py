"""Serve program: every gap between consecutive output tokens of a
request scheduled in the window, 99th percentile.  A request's first
token is out at the end of its ``serve.attach``, each later one at the
end of a ``serve.step`` whose ``live`` holds it.  Program spans."""
from .. import spans
from ..stats import percentile


def read(run):
    sp = spans.of(run)
    if sp is None:
        return None
    later = {}
    for st in sp.named("serve.step"):
        for rid in st[5]["live"]:
            later.setdefault(rid, []).append(st[2])
    gaps = []
    for (attach,) in sp.per_request(run.records, "serve.attach"):
        ts = [attach[2]] + sorted(later.get(attach[5]["req"], ()))
        gaps += [b - a for a, b in zip(ts, ts[1:])]
    return percentile(gaps, 99) * 1e-6 if gaps else None
