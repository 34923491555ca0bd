"""The full layers' chunk attention's share of its roofline in the MiMo-V2
block: the least time the chip could take for the attention of the traced
window's prefill chunks in the full layers (`benchmark/flops_mimo.py`:
causal over each chunk's real tokens and its cached prefix, every query head,
QK^T at the q/k width and PV at the v width; a layer's K and V rows of prefix
and chunk read once) over the mean device time under ``scopes`` in the
launches of ``programs``, in the style of `paged_prefill_roofline`.

The work comes from the program's own `prefill_chunk` spans (span store,
replica ``replica``) that opened after the trace began: ``start`` and
``tokens``.  Both sides are means, so a launch cut by the trace's edge moves
neither.  The bound that sets the least time is noted.

Nothing to read (None): no device plane, no span store, or a program that
writes no names.  A program that does, with no launch under the scopes or no
`prefill_chunk` span in the traced window, is an error."""
from benchmark import flops, flops_mimo, xplane_raw
from benchmark.readers import scope_ms
from benchmark.readers.latent_prefill_roofline import traced_chunks


def read(run, replica, programs, scopes):
    raw = xplane_raw.of_run(run)
    chunks = traced_chunks(run, replica)
    if raw is None or chunks is None:
        return None
    want = set(scopes)
    seconds = [sum(s for s, _, stack in ops if want.intersection(stack))
               for ops in scope_ms._launches(run, raw, programs)]
    busy = [s for s in seconds if s > 0]
    if not busy or not chunks:
        what = "%d launches of %s under %s, %d prefill_chunk spans in the " \
            "traced window" % (len(busy), programs, scopes, len(chunks))
        if xplane_raw.named(raw):
            raise ValueError("mimo_prefill_roofline: %s, in a trace that "
                             "holds the program's other names" % what)
        run.note("mimo_prefill_roofline: %s" % what)
        return None
    least, bounds = 0.0, {}
    for start, tokens in chunks:
        t, bound = flops.roofline_seconds(
            *flops_mimo.full_prefill(run.cfg, start, tokens), run.peaks)
        least += t
        bounds[bound] = bounds.get(bound, 0) + 1
    least /= len(chunks)
    mean = sum(busy) / len(busy)
    run.note("full-layer chunk attention: %d traced launches, %.3f ms each "
             "on the device; %d chunks of %.0f tokens over a prefix of %.0f, "
             "least %.3f ms, bound by %s"
             % (len(busy), 1e3 * mean, len(chunks),
                sum(n for _, n in chunks) / len(chunks),
                sum(s for s, _ in chunks) / len(chunks), 1e3 * least,
                max(bounds, key=bounds.get)))
    return 100.0 * least / mean
