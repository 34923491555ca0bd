"""The host's milliseconds an iteration in the scheduler's phases, read from
the program's span store.  Each `iteration` record carries `phase_ms`: the
host time of every phase of the scheduler (`sweep`, `prefill`, `admit`,
`grow`, `pack`, `launch`, `fetch`, `publish`, `account`, `wait`) since the
record before, so the records tile the scheduler's time.

``part``: `untraced`, the window's records from its opening to the instant
the profiler's trace started (the engine as an untraced run times it), or
`traced`, from there to the close (the engine under the profiler); the
value is the mean over that part's records of the sum of ``phases``.
`ratio` is the traced part's mean over the untraced part's: what the
profiler itself costs the host.  The whole table of both parts is noted on
standard error.

Nothing to read (None): a program whose store holds no `iteration` record
with `phase_ms` (one from before it).  An error: a store that holds such
records and none in the part, or whose ring has lost the window's start
(its oldest span closed after the window opened)."""
from benchmark.readers.span_percentile import window


def parts(run, replica):
    """{part: [phase_ms of each record]}, or None where the store holds no
    record with `phase_ms`; read once a run."""
    from mxnet_tpu import tracing

    if not hasattr(run, "_phase_parts"):
        run._phase_parts = {}
    if replica in run._phase_parts:
        return run._phase_parts[replica]
    ring = [r for r in tracing.snapshot(replica) if r.get("type") == "span"]
    if not any("phase_ms" in r.get("attrs", {}) for r in ring
               if r["phase"] == "iteration"):
        run.note("phase_host_ms: no iteration record carries phase_ms (a "
                 "program from before them)")
        run._phase_parts[replica] = None
        return None
    if ring[0]["t1"] >= run.t_open:
        raise ValueError(
            "phase_host_ms: the span ring of %r holds %d records, the "
            "oldest closed %.3fs after the window opened: it has lost the "
            "window's start" % (replica, len(ring),
                                ring[0]["t1"] - run.t_open))
    t_trace = getattr(run, "_t_trace", run.t_close)
    out = {"untraced": [], "traced": []}
    for r in window(run, replica) or ():
        ms = r.get("attrs", {}).get("phase_ms")
        if r["phase"] == "iteration" and ms is not None:
            out["untraced" if r["t1"] < t_trace else "traced"].append(ms)
    run._phase_parts[replica] = out
    for name, records in out.items():
        keys = sorted({k for ms in records for k in ms})
        run.note("host ms an iteration by phase, %s (%d records): %s"
                 % (name, len(records), ", ".join(
                     "%s %.3f" % (k, sum(ms.get(k, 0.0) for ms in records)
                                  / len(records)) for k in keys)))
    return out


def mean(records, phases, part):
    if not records:
        raise ValueError("phase_host_ms: the span store holds iteration "
                         "records with phase_ms and none in the %s part of "
                         "the window" % part)
    # a phase the records lack is an error: a rename must not read as 0
    return sum(sum(ms[p] for p in phases) for ms in records) / len(records)


def read(run, replica, phases, part="untraced"):
    got = parts(run, replica)
    if got is None:
        return None
    if part != "ratio":
        return mean(got[part], phases, part)
    base = mean(got["untraced"], phases, "untraced")
    if base <= 0:
        raise ValueError("phase_host_ms: the untraced part spends no time "
                         "in %s" % (phases,))
    return mean(got["traced"], phases, "traced") / base
