"""Device milliseconds of one launch that the program gave no name: the
operations that carry no scope at all, the loops' own events apart.

`scope_ms` with ``unscoped`` counts every operation whose name stack is
empty.  On this runtime a `while` (or `conditional`) event spans its steps'
operations and carries no name stack, whatever scope the loop was traced
under (the compiled program names it `moe_loop/while`; the device trace does
not): under `scope_ms` a launch whose experts run in a loop of hundreds of
steps reads the whole loop as unnamed, beside the same time under the steps'
own scopes.  This reader leaves those wrappers out and gives the median over
the launches of ``programs`` of what is left: the compiler's clones and
copies that lost their metadata.

Nothing to read (None): no device plane, or a program that writes no names
(`xplane_raw.named`).  A program that does, with no launch of ``programs``,
is an error."""
import statistics

from benchmark import trace, xplane_raw
from benchmark.readers import scope_ms

#: events that span other events of the same launch
WRAPPERS = ("while", "conditional", "call")


def read(run, programs):
    raw = xplane_raw.of_run(run)
    if raw is None:
        return None
    launches = scope_ms._launches(run, raw, programs)
    if not launches:
        what = "no launch of %s" % (programs,)
        if xplane_raw.named(raw):
            raise ValueError("unscoped_ms: %s, in a trace that holds the "
                             "program's other names" % what)
        run.note("unscoped_ms: %s (a program that writes no names)" % what)
        return None
    kinds, sums, spans = {}, [], 0.0
    for ops in launches:
        total = 0.0
        for seconds, display, stack in ops:
            if stack:
                continue
            kind = trace.kind_of(display)
            if kind in WRAPPERS:
                spans += seconds
                continue
            kinds[kind] = kinds.get(kind, 0.0) + seconds
            total += seconds
        sums.append(total)
    top = sorted(kinds.items(), key=lambda kv: -kv[1])[:6]
    run.note("under no scope, loops' own events (%.4fs) apart: %.4fs in %d "
             "launches, median %.3f ms, by operation: %s"
             % (spans, sum(sums), len(sums), 1e3 * statistics.median(sums),
                ", ".join("%s %.4f" % kv for kv in top)))
    return 1e3 * statistics.median(sums)
