"""The routed experts' products' share of their roofline: the least time the
chip could take for the (row, expert) pairs the traced launches really
routed to held experts (`benchmark/flops_latent_moe.py`: three products a
pair; each expert that has a row read once a layer, the rows in and out) over
the device time under ``scopes`` in all launches of ``programs``.

The work comes from the program's `iteration` records that lie wholly inside
the traced part of the window (``expert_rows``, ``expert_hits``; a chunk's
counts ride in the record of the next iteration that launched a decode), the
time from every operation in the trace: at the trace's edges work is left
out and time is not, so the share reads low rather than high.  The bound
that sets the least time is noted.

Nothing to read (None): no device plane, no span store, or a program that
writes no names.  A program that does, and lacks these, is an error."""
from benchmark import flops, flops_latent_moe, xplane_raw
from benchmark.readers import scope_ms
from benchmark.readers.latent_decode_roofline import traced_iterations


def read(run, replica, programs, scopes):
    raw = xplane_raw.of_run(run)
    attrs = traced_iterations(run, replica)
    if raw is None or attrs is None:
        return None
    want = set(scopes)
    seconds = sum(s for ops in scope_ms._launches(run, raw, programs)
                  for s, _, stack in ops if want.intersection(stack))
    work = [a for a in attrs if "expert_hits" in a]
    pairs = sum(a["expert_rows"] for a in work)
    hits = sum(a["expert_hits"] for a in work)
    if not seconds or not pairs:
        what = "%.4fs under %s in %s, %d iteration records with %d pairs" \
            % (seconds, scopes, programs, len(work), pairs)
        if xplane_raw.named(raw):
            raise ValueError("expert_roofline: %s, in a trace that holds "
                             "the program's other names" % what)
        run.note("expert_roofline: %s" % what)
        return None
    least, bound = flops.roofline_seconds(
        *flops_latent_moe.expert_products(run.cfg, pairs, hits), run.peaks)
    run.note("routed experts: %d pairs over %d (layer, expert) matrix sets "
             "in %d records; %.4fs on the device, least %.4fs, bound by %s"
             % (pairs, hits, len(work), seconds, least, bound))
    return 100.0 * least / seconds
