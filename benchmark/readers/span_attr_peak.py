"""The largest value of one attribute over the window's spans of one
``phase``, as a share (%) of the cell's ``of`` (a key of the cell's file):
`blocks_live` of the scheduler's `iteration` records over `n_blocks` is the
pool's peak occupancy by sequences, apart from what the prefix cache parks.

Nothing to read (None): a program whose store has no `window()`.  A store
that has it and holds no such span, or none with the attribute, is an
error."""
from benchmark.readers.span_percentile import values


def read(run, replica, phase, attr, of):
    got = values(run, replica, phase,
                 lambda r: r.get("attrs", {}).get(attr))
    if got is None:
        return None
    return 100.0 * max(got) / float(run.cell[of])
