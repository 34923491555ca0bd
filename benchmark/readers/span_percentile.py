"""A percentile, in milliseconds, of the durations of the program's own spans
of one ``phase`` that closed inside the window: `tracing.window(replica,
run.t_open, run.t_close)` of the program's span store, which outlives the
engine (both clocks are `time.perf_counter`).

Nothing to read (None): a program whose store has no `window()` (one from
before it), or a run with no window.  A store that has it and holds no span
of that phase in the window is an error."""
import numpy as np


def window(run, replica):
    """The window's span records, read from the store once a run; None
    where there is no store to read."""
    from mxnet_tpu import tracing

    if not hasattr(tracing, "window") or run.t_close is None:
        return None
    if not hasattr(run, "_spans"):
        run._spans = {}
    if replica not in run._spans:
        run._spans[replica] = tracing.window(replica, run.t_open,
                                             run.t_close)
    return run._spans[replica]


def values(run, replica, phase, of):
    """``of(record)`` of the window's spans of ``phase`` (None values left
    out); None where there is no store, an error where there is no value."""
    records = window(run, replica)
    if records is None:
        return None
    out = [of(r) for r in records if r["phase"] == phase]
    out = [v for v in out if v is not None]
    if not out:
        raise ValueError(
            "the span store of %r holds %d records of the window and "
            "nothing to read of phase %r" % (replica, len(records), phase))
    return out


def read(run, replica, phase, q):
    ms = values(run, replica, phase, lambda r: r["ms"])
    if ms is None:
        return None
    return float(np.percentile(np.asarray(ms, np.float64), q))
