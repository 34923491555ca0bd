"""The delta rule's decode step's share of its roofline in the OLMo hybrid
block's linear layers: the least time the chip could take for the traced
decode launches' step (`benchmark/flops_olmo_hybrid.py`: each row's float32
state read once and written once in every linear layer, its q, k, v, o,
decay and write strength in and out) over the device time under ``scopes``
in the launches of ``programs`` (the rule's own scope and the slots' read
and write, which together move the state).

The work comes from the program's own `iteration` records (span store,
replica ``replica``) that lie wholly inside the traced part of the window:
``rows`` (the launch's real rows; padding rows are not counted).  Both
sides are means over their launches, so a launch cut by the trace's edge
moves neither.  The bound that sets the least time is noted.

Nothing to read (None): no device plane, no span store, or a program that
writes no names.  A program that does, with no launch under the scopes or no
record with ``rows``, is an error."""
from benchmark import flops, flops_olmo_hybrid, xplane_raw
from benchmark.readers import scope_ms
from benchmark.readers.latent_decode_roofline import traced_iterations


def read(run, replica, programs, scopes):
    raw = xplane_raw.of_run(run)
    attrs = traced_iterations(run, replica)
    if raw is None or attrs is None:
        return None
    want = set(scopes)
    seconds = [sum(s for s, _, stack in ops if want.intersection(stack))
               for ops in scope_ms._launches(run, raw, programs)]
    seconds = [s for s in seconds if s > 0]
    rows = [a["rows"] for a in attrs if "ctx_blocks" in a]
    if not seconds or not rows:
        what = "%d launches of %s under %s, %d decode iteration records" \
            % (len(seconds), programs, scopes, len(rows))
        if xplane_raw.named(raw):
            raise ValueError("olmo_delta_decode_roofline: %s, in a trace "
                             "that holds the program's other names" % what)
        run.note("olmo_delta_decode_roofline: %s" % what)
        return None
    least, bounds = 0.0, {}
    for n in rows:
        t, bound = flops.roofline_seconds(
            *flops_olmo_hybrid.delta_decode(run.cfg, n), run.peaks)
        least += t
        bounds[bound] = bounds.get(bound, 0) + 1
    least /= len(rows)
    mean = sum(seconds) / len(seconds)
    run.note("delta-rule decode step: %d traced launches, %.3f ms each on "
             "the device under %s; %d records, %.1f rows a launch, least "
             "%.3f ms, bound by %s"
             % (len(seconds), 1e3 * mean, scopes, len(rows),
                sum(rows) / len(rows), 1e3 * least,
                max(bounds, key=bounds.get)))
    return 100.0 * least / mean
