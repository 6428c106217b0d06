"""Edat runtime (the ``decode_tick`` chain): mean time a ``serve.decode``
task that stepped the batch (it holds a ``serve.step`` span) waited for
the ``server`` lock, 0 where it took it at once, over the window.
Program spans."""
from .. import spans


def read(run):
    sp = spans.of(run)
    if sp is None:
        return None
    xs = [sp.lock_wait(t) for t in sp.in_window("edat.task")
          if t[5]["task"] == "serve.decode"
          and sp.children(t, "serve.step")]
    return sum(xs) / len(xs) * 1e-6 if xs else None
