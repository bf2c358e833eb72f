"""Device-idle time inside the engine's ``engine.step`` spans but outside
its ``engine.read`` spans, per step, in the traced window: the engine's own
host work (admission, inputs, bookkeeping) with the chip idle.  The
harness's time between steps is not in it, so with ``read_wait_ms`` it
sums to at most ``tick_host_ms``."""
from engine_spans import idle_split


def read(run):
    got = idle_split(run.trace)
    if got is None:
        return None
    n, step_idle, read_idle = got
    return (step_idle - read_idle) / n * 1e-6
