"""The mean of one attribute over the window's spans of one ``phase``, of
those whose other attributes equal ``where``: `expert_hits` of the
scheduler's `iteration` records that launched no prefill chunk (``chunks``
0) is the (layer, expert) matrix sets one decode launch reads.

Nothing to read (None): a program whose store has no `window()`, or whose
records do not carry the attribute (one from before it).  A store that holds
no span of the phase is an error."""
from benchmark.readers.span_percentile import values


def read(run, replica, phase, attr, where=None):
    where = where or {}

    def of(r):
        attrs = r.get("attrs", {})
        if any(attrs.get(k) != v for k, v in where.items()):
            return False
        return attrs.get(attr, False)

    got = values(run, replica, phase, of)
    if got is None:
        return None
    got = [v for v in got if v is not False]
    return sum(got) / float(len(got)) if got else None
