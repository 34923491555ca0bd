"""Device milliseconds of one launch under named scopes of the program.

An operation's `tf_op` (read from the raw trace, `benchmark/xplane_raw.py`)
is the JAX name stack it was traced under: `jax.named_scope`s, symbol node
names, kernel names.  For every launch of ``programs`` (a module event of
that name) this sums the device time of the operations that ran inside it
(by their place in time) and whose name stack passes through one of
``scopes``, forward or backward (`transpose(jvp(pred))` is `pred`), and
gives the median over the launches: a launch that the trace's end cuts
short is one low reading among many, not a share of the sum.

``unscoped``: the operations that carry no scope at all instead — the
compiler's own clones (`.remat`) and copies lose their metadata, so time
can pass between a named operation and its unnamed clone with no change
of the program; a metric of the scoped time is read beside this one.

It also notes the whole table, once for each set of programs — device
seconds by outermost scope, digits folded (`layerN_q`) — so a traced run's
standard error says where the time goes in the model's own names.

Nothing to read (None): no device plane, or a program that writes no names
(`xplane_raw.named`).  A program that does, and lacks these, is an error."""
import bisect
import re
import statistics

from benchmark import trace, xplane_raw


def launches_of(ops, modules):
    """[[operation]] for each module event: the operations that start
    inside it."""
    starts = [op[0] for op in ops]
    return [ops[bisect.bisect_left(starts, s):bisect.bisect_left(starts,
                                                                 s + d)]
            for s, d, _ in modules]


def stacks(ops):
    """[(seconds, display name, the scopes around the operation)]: the name
    stack without the program's own name and the primitive's."""
    return [(d / 1e9, meta.get("display_name") or "?",
             xplane_raw.scopes_of(meta.get("tf_op"))[1:-1])
            for _, d, meta in ops]


def by_scope(ops):
    """{outermost scope, digits folded: seconds}; operations that carry no
    scope go under "(no scope)"."""
    table = {}
    for seconds, _, stack in stacks(ops):
        top = re.sub(r"\d+", "N", stack[0]) if stack else "(no scope)"
        table[top] = table.get(top, 0.0) + seconds
    return table


def _launches(run, raw, programs):
    """`stacks()` of each launch's operations, worked out and noted once a
    run for each set of programs."""
    cache = run.__dict__.setdefault("_scope_ms", {})
    key = tuple(programs)
    if key not in cache:
        modules = xplane_raw.programs_of(raw, programs)
        launches = launches_of(raw["ops"], modules)
        cache[key] = [stacks(ops) for ops in launches]
        if modules:
            ops = [op for launch in launches for op in launch]
            table = sorted(by_scope(ops).items(), key=lambda kv: -kv[1])
            run.note("device seconds by scope in %d launches of %s (%.4fs): "
                     "%s" % (len(modules), programs,
                             sum(d for _, d, _ in ops) / 1e9,
                             ", ".join("%s %.4f" % kv for kv in table[:16])))
    return cache[key]


def read(run, programs, scopes=(), unscoped=False):
    raw = xplane_raw.of_run(run)
    if raw is None:
        return None
    launches = _launches(run, raw, programs)
    want = set(scopes)
    kinds, sums = {}, []
    for ops in launches:
        total = 0.0
        for seconds, display, stack in ops:
            if (not stack) if unscoped else want.intersection(stack):
                k = trace.kind_of(display)
                kinds[k] = kinds.get(k, 0.0) + seconds
                total += seconds
        sums.append(total)
    if not kinds and not (unscoped and launches):
        what = "no launch of %s" % (programs,) if not launches \
            else "no operation under %s in %s" % (scopes, programs)
        if xplane_raw.named(raw):
            raise ValueError("scope_ms: %s, in a trace that holds the "
                             "program's other names" % what)
        run.note("scope_ms: %s (a program that writes no names)" % what)
        return None
    top = sorted(kinds.items(), key=lambda kv: -kv[1])[:6]
    run.note("under %s: %.4fs in %d launches, median %.3f ms, by operation: "
             "%s" % ("no scope" if unscoped else scopes, sum(sums),
                     len(sums), 1e3 * statistics.median(sums),
                     ", ".join("%s %.4f" % kv for kv in top)))
    return 1e3 * statistics.median(sums)
