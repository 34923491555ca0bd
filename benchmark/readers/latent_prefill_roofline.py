"""The latent prefill attention's share of its roofline: the least time the
chip could take for the attention of the traced window's prefill chunks over
the device time under ``scopes`` in the launches of ``programs``.

The work comes from the program's own `prefill_chunk` spans (span store,
replica ``replica``) that opened after the trace began: ``start`` (the
cached prefix the chunk attends to) and ``tokens`` (its real tokens, not its
bucket).  A chunk's FLOPs are `flops_latent_moe.attn_flops_token` over the
contexts of its tokens, ``tokens * start + tokens * (tokens + 1) / 2``:
causal, in the expanded form, all layers, with no credit for expanding a
cached row to its heads' keys and values again (at these widths a kernel
that re-expands every row for every chunk does 3.87 operations for each 2.15
counted, so it cannot read over 56 %).  Its bytes are, a layer, the latent
rows of prefix and chunk read once and each head's queries in and values
out.  Both sides are means over their launches, so a launch cut by the
trace's edge moves neither.  The bound that sets the least time is noted,
and so is the median device time by bucket and, where the spans and the
launches pair off in order, by prefix.

Nothing to read (None): no device plane, no span store, or a program that
writes no names.  A program that does, with no launch under the scopes or no
`prefill_chunk` span in the traced window, is an error."""
import re
import statistics

from benchmark import flops, flops_latent_moe, xplane_raw
from benchmark.readers import scope_ms, span_percentile


def chunk_work(cfg, start, tokens, itemsize=2):
    """(FLOPs, bytes) of one chunk's attention, all layers."""
    context = tokens * start + tokens * (tokens + 1) / 2.0
    per_head = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
                + cfg["v_head_dim"])
    nbytes = cfg["num_hidden_layers"] * (
        (start + tokens) * flops_latent_moe.latent_row_bytes(cfg, itemsize)
        + tokens * cfg["num_attention_heads"] * per_head * itemsize)
    return flops_latent_moe.attn_flops_token(cfg, context), float(nbytes)


def traced_chunks(run, replica):
    """(start, tokens) of the window's `prefill_chunk` spans that opened
    after the trace began (None: no store, or an untraced run)."""
    records = span_percentile.window(run, replica)
    t_trace = getattr(run, "_t_trace", None)
    if records is None or t_trace is None:
        return None
    return [(r["attrs"]["start"], r["attrs"]["tokens"]) for r in records
            if r["phase"] == "prefill_chunk" and r["t0"] >= t_trace
            and "start" in r.get("attrs", {})]


def _table(rows):
    """"key n x median ms" of [(key, seconds)], keys in order."""
    groups = {}
    for key, s in rows:
        groups.setdefault(key, []).append(s)
    return ", ".join("%s %d x %.2f ms" % (k, len(v),
                                          1e3 * statistics.median(v))
                     for k, v in sorted(groups.items()))


def _note_where(run, raw, programs, seconds, chunks):
    """The scopes' device time by the launches' bucket, and by prefix where
    the chunks' spans and the launches pair off one to one in order (each
    span's tokens fit its launch's bucket and not the next smaller one in
    use)."""
    buckets = [int(re.search(r"(\d+)", name[len("jit_"):]).group(1))
               for _, _, name in xplane_raw.programs_of(raw, programs)]
    launched = [(b, s) for b, s in zip(buckets, seconds) if s > 0]
    run.note("latent prefill attention by bucket: %s" % _table(launched))
    sizes = sorted(set(buckets))
    fits = [min((b for b in sizes if b >= n), default=None)
            for _, n in chunks]
    if fits != [b for b, _ in launched]:
        return
    run.note("latent prefill attention by prefix (thousands of tokens, "
             "whole chunks of %d): %s"
             % (sizes[-1], _table([(start // 1000, s) for (start, n), (b, s)
                                   in zip(chunks, launched)
                                   if b == sizes[-1]])))


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
            raise ValueError("latent_prefill_roofline: %s, in a trace that "
                             "holds the program's other names" % what)
        run.note("latent_prefill_roofline: %s" % what)
        return None
    least, bounds = 0.0, {}
    for start, tokens in chunks:
        t, bound = flops.roofline_seconds(
            *chunk_work(run.cfg, start, tokens), run.peaks)
        least += t
        bounds[bound] = bounds.get(bound, 0) + 1
    least /= len(chunks)
    mean = sum(busy) / len(busy)
    run.note("latent prefill attention: %d traced launches, %.3f ms each on "
             "the device; %d chunks of %.0f tokens over a prefix of %.0f, "
             "least %.3f ms, bound by %s"
             % (len(busy), 1e3 * mean, len(chunks),
                sum(n for _, n in chunks) / len(chunks),
                sum(s for s, _ in chunks) / len(chunks), 1e3 * least,
                max(bounds, key=bounds.get)))
    _note_where(run, raw, programs, seconds, chunks)
    return 100.0 * least / mean
