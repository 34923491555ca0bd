"""The paged decode-attention kernel's share of its roofline in the MiMo-V2
block's full layers: the least time the chip could take for the traced decode
launches' full-layer attention (FLOPs and bytes from `benchmark/flops_mimo.py`:
each row's live blocks' K at the q/k width and V at the v width read once a
full layer, in the pool's dtype) over the device time under ``scopes`` in the
launches of ``programs``.

The work comes from the program's own `iteration` records (span store,
replica ``replica``) that lie wholly inside the traced part of the window:
``full_ctx_blocks`` (the blocks the launch's full layers read, all of them)
and ``rows``.  Both sides are means over their launches, so a launch cut by
the trace's edge moves neither.  The bound that sets the least time is noted.

Nothing to read (None): no device plane, no span store, or a program that
writes no names.  A program that does, with no launch under the scopes or no
record with ``full_ctx_blocks``, is an error."""
from benchmark import flops, flops_mimo, xplane_raw
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
    work = [(a["full_ctx_blocks"], a["rows"]) for a in attrs
            if "full_ctx_blocks" in a]
    if not seconds or not work:
        what = "%d launches of %s under %s, %d iteration records with " \
            "full_ctx_blocks" % (len(seconds), programs, scopes, len(work))
        if xplane_raw.named(raw):
            raise ValueError("mimo_decode_roofline: %s, in a trace that "
                             "holds the program's other names" % what)
        run.note("mimo_decode_roofline: %s" % what)
        return None
    cfg = run.cfg
    bs, layers = cfg["engine"]["block_size"], flops_mimo.full_layers(cfg)
    least, bounds = 0.0, {}
    for blocks, rows in work:
        t, bound = flops.roofline_seconds(
            *flops_mimo.gqa_decode(cfg, blocks * bs, rows * layers),
            run.peaks)
        least += t
        bounds[bound] = bounds.get(bound, 0) + 1
    least /= len(work)
    mean = sum(seconds) / len(seconds)
    run.note("full-layer paged decode attention: %d traced launches, %.3f ms "
             "each on the device; %d records, %.0f live blocks a launch in "
             "all full layers, least %.3f ms, bound by %s"
             % (len(seconds), 1e3 * mean, len(work),
                sum(b for b, _ in work) / len(work), 1e3 * least,
                max(bounds, key=bounds.get)))
    return 100.0 * least / mean
