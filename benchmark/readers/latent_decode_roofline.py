"""The latent decode-attention kernel's share of its roofline: the least time
the chip could take for the traced decode launches' attention (FLOPs and bytes
from `benchmark/flops_latent_moe.py`: expanded-form operations, each live
latent block read once a layer) over the device time under ``scopes`` in the
launches of ``programs``.

The work comes from the program's own `iteration` records (span store,
replica ``replica``) that lie wholly inside the traced part of the window:
``ctx_blocks`` (table entries the launch's rows walk) and ``rows``.  Both
sides are means over their launches, so a launch cut by the trace's edge
moves neither.  The bound that sets the least time is noted.

Nothing to read (None): no device plane, no span store, or a program that
writes no names.  A program that does, with no launch under the scopes or no
record with ``ctx_blocks``, is an error."""
from benchmark import flops, flops_latent_moe, xplane_raw
from benchmark.readers import scope_ms, span_percentile


def traced_iterations(run, replica):
    """The window's `iteration` records that opened after the trace began
    (None: no store, or an untraced run)."""
    records = span_percentile.window(run, replica)
    t_trace = getattr(run, "_t_trace", None)
    if records is None or t_trace is None:
        return None
    return [r.get("attrs", {}) for r in records
            if r["phase"] == "iteration" and r["t0"] >= t_trace]


def read(run, replica, programs, scopes):
    raw = xplane_raw.of_run(run)
    attrs = traced_iterations(run, replica)
    if raw is None or attrs is None:
        return None
    want = set(scopes)
    seconds = [sum(s for s, _, stack in ops if want.intersection(stack))
               for ops in scope_ms._launches(run, raw, programs)]
    seconds = [s for s in seconds if s > 0]
    work = [(a["ctx_blocks"], a["rows"]) for a in attrs if "ctx_blocks" in a]
    if not seconds or not work:
        what = "%d launches of %s under %s, %d iteration records with " \
            "ctx_blocks" % (len(seconds), programs, scopes, len(work))
        if xplane_raw.named(raw):
            raise ValueError("latent_decode_roofline: %s, in a trace that "
                             "holds the program's other names" % what)
        run.note("latent_decode_roofline: %s" % what)
        return None
    cfg = run.cfg
    bs, layers = cfg["engine"]["block_size"], cfg["num_hidden_layers"]
    least, bounds = 0.0, {}
    for blocks, rows in work:
        t, bound = flops.roofline_seconds(
            *flops_latent_moe.latent_decode(cfg, blocks * bs, rows),
            run.peaks)
        least += layers * t
        bounds[bound] = bounds.get(bound, 0) + 1
    least /= len(work)
    mean = sum(seconds) / len(seconds)
    run.note("latent decode attention: %d traced launches, %.3f ms each on "
             "the device; %d records, %.0f live blocks a launch, least %.3f "
             "ms, bound by %s"
             % (len(seconds), 1e3 * mean, len(work),
                sum(b for b, _ in work) / len(work), 1e3 * least,
                max(bounds, key=bounds.get)))
    return 100.0 * least / mean
