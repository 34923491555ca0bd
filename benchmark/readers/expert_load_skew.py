"""How unevenly the routed rows fall on the experts held here: over the
window's `iteration` records, the fullest expert's rows (``expert_load_max``)
over the mean expert's (``expert_rows`` over the ``held`` experts, a key of
the configuration).  1 is an even spread; with a handful of rows a launch
over a dozen experts it is several.

Nothing to read (None): no span store.  A store whose records lack the
attributes is an error."""
from benchmark.readers.span_percentile import values


def read(run, replica, phase, held):
    got = values(run, replica, phase, lambda r: (
        r["attrs"]["expert_load_max"], r["attrs"]["expert_rows"])
        if "expert_rows" in r.get("attrs", {}) else None)
    if got is None:
        return None
    rows = sum(r for _, r in got)
    if not rows:
        return None
    return sum(m for m, _ in got) * float(run.cfg[held]) / rows
