"""Device-idle time inside the engine's ``engine.read`` spans (the blocking
reads of each tick's tokens), per ``engine.step`` span, in the traced
window: how long each tick waits on the device-to-host token read with the
chip idle."""
from engine_spans import idle_split


def read(run):
    got = idle_split(run.trace)
    if got is None:
        return None
    n, _, read_idle = got
    return read_idle / n * 1e-6
