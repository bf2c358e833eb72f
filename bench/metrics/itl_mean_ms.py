"""Mean of every gap between consecutive output tokens of a request, over
all gaps that end in the window: the pace a user reads at."""


def read(run):
    t0, t1 = run.window
    gaps = [b - a for g in run.requests for a, b in zip(g.times, g.times[1:])
            if t0 < b <= t1]
    if not gaps:
        return None
    return sum(gaps) / len(gaps) * 1e3
