"""1 - the union of the device-operation intervals over the traced window."""


def read(run):
    r = run.reduced
    if r is None or not r["busy_s"]:
        return None
    return 100.0 * (1.0 - r["busy_s"] / r["window_s"])
