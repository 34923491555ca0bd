"""One counter of the window over another (or over the window's length),
times ``scale``."""


def read(run, num, den, scale=1.0):
    top = run.counters.get(num)
    bottom = run.window_s if den == "window_s" else run.counters.get(den)
    if top is None or not bottom:
        return None
    return scale * float(top) / float(bottom)
