"""Where the device's idle time falls among the scheduler's phases.

The program wraps each phase of a scheduler iteration in a span written into
the profiler's own trace (`sched.sweep`, `sched.prefill`, `sched.admit`,
`sched.grow`, `sched.pack`, `sched.launch`, `sched.fetch`, `sched.publish`,
inside one `sched.iteration`), on the clock of the device's operations.  The
device's idle time is the gaps in the union of its operations' intervals
(`trace.busy_union`, as `device_idle` reads it, between the first and the
last operation).  This intersects the two.

``spans``: idle milliseconds inside spans of these names, per iteration (a
`sched.iteration` that holds a `sched.launch`).  ``outside``: the share, in
%, of the idle time that lies inside no phase at all.  The whole table (idle
seconds by phase) is noted on standard error.

Nothing to read (None): no device plane, or a program that writes no names
(`xplane_raw.named`).  A program that does, with no `sched.launch` span in
the trace, is an error."""
from benchmark import trace, xplane_raw

ITERATION, LAUNCH = "sched.iteration", "sched.launch"


def overlap(gaps, spans):
    """Nanoseconds of the sorted, disjoint ``gaps`` [(lo, hi)] that lie
    inside the sorted, disjoint ``spans`` [(lo, hi)]."""
    total = i = 0
    for lo, hi in spans:
        while i < len(gaps) and gaps[i][1] <= lo:
            i += 1
        j = i
        while j < len(gaps) and gaps[j][0] < hi:
            total += min(hi, gaps[j][1]) - max(lo, gaps[j][0])
            j += 1
    return total


def table(raw):
    """({phase: idle seconds}, idle seconds in all, iterations that
    launched), or None where the trace holds no phase span."""
    phases = {}
    for s, d, name, _ in raw["spans"]:
        if name.startswith("sched."):
            phases.setdefault(name, []).append((s, s + d))
    if LAUNCH not in phases:
        return None
    gaps = trace.busy_union(raw["ops"])[2]
    idle = sum(hi - lo for lo, hi in gaps)
    launches = [lo for lo, _ in phases[LAUNCH]]
    iterations = sum(1 for lo, hi in phases.get(ITERATION, ())
                     if any(lo <= t < hi for t in launches))
    by_phase = {name: overlap(gaps, spans) / 1e9
                for name, spans in phases.items() if name != ITERATION}
    return by_phase, idle / 1e9, iterations


def read(run, spans=None, outside=False):
    raw = xplane_raw.of_run(run)
    if raw is None:
        return None
    if not hasattr(run, "_idle_table"):
        run._idle_table = table(raw)
        if run._idle_table is None:
            if xplane_raw.named(raw):
                raise ValueError("idle_in_spans: no %s span, in a trace "
                                 "that holds the program's other names"
                                 % LAUNCH)
            run.note("idle_in_spans: the trace holds no sched.* span (a "
                     "program that writes no names)")
        else:
            by_phase, idle, n = run._idle_table
            run.note("device idle %.4fs over %d iterations, by phase: %s; "
                     "in no phase %.4f"
                     % (idle, n, ", ".join(
                         "%s %.4f" % kv for kv in sorted(by_phase.items())),
                        idle - sum(by_phase.values())))
    if run._idle_table is None:
        return None
    by_phase, idle, iterations = run._idle_table
    if outside:
        return 100.0 * (idle - sum(by_phase.values())) / idle if idle \
            else None
    if not iterations:
        return None
    return 1e3 * sum(by_phase.get(name, 0.0) for name in spans) / iterations
