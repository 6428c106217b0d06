"""Engine: mean host time of a decode step outside its token read,
``engine.step`` minus its ``engine.step.read`` (the uploads, the jitted
call's dispatch and the token bookkeeping), over the window.  Program
spans."""
from .. import spans


def read(run):
    sp = spans.of(run)
    if sp is None:
        return None
    xs = [spans.dur(s) - sum(spans.dur(k) for k in
                             sp.children(s, "engine.step.read"))
          for s in sp.in_window("engine.step")]
    return sum(xs) / len(xs) * 1e-6 if xs else None
