"""A percentile, in milliseconds, of the durations of the spans of one name
that the program writes into the profiler's trace (`xplane_raw.
PROGRAM_SPANS`): `train_step` is the host's time in one
`SPMDTrainer.step()` — placing the batch, the retrace watchdog, the
dispatch, and any wait for the device's queue — so its median beside the
device's time for a step says how far the host is from holding the chip
back.

Nothing to read (None): no device plane, or a program that writes no names
(`xplane_raw.named`).  A program that does, with no span of this name, is
an error."""
import numpy as np

from benchmark import xplane_raw


def read(run, span, q):
    raw = xplane_raw.of_run(run)
    if raw is None:
        return None
    ms = [d / 1e6 for _, d, name, _ in raw["spans"] if name == span]
    if not ms:
        if xplane_raw.named(raw):
            raise ValueError("span_ms: no %s span, in a trace that holds "
                             "the program's other names" % span)
        run.note("span_ms: no %s span (a program that writes no names)"
                 % span)
        return None
    value = float(np.percentile(np.asarray(ms, np.float64), q))
    run.note("spans %s: %d, %.4fs on the host, p%g %.3f ms"
             % (span, len(ms), sum(ms) / 1e3, q, value))
    return value
