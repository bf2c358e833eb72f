"""Output tokens emitted in the window over the window's seconds.  A
token counts when the tick that emitted it ends inside the window."""


def read(run):
    t0, t1 = run.window
    n = sum(1 for g in run.requests for t in g.times if t0 < t <= t1)
    return n / (t1 - t0)
