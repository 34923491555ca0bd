"""The paged prefill attention's share of its roofline over a K/V-pair pool
(GPT-2's, one head a K/V head, or LFM2's, grouped-query): the least time the
chip could take for the attention of the traced window's prefill chunks over
the mean device time under ``scopes`` in the launches of ``programs``.

The work comes from the program's own `prefill_chunk` spans (span store,
replica ``replica``) that opened after the trace began: ``start`` (the
cached prefix the chunk attends to) and ``tokens`` (its real tokens, not its
bucket).  A chunk's FLOPs are QK^T and PV over each real token's own
context, causal, every query head, every attention layer:
``4 * heads * head * layers * (tokens * start + tokens * (tokens + 1) / 2)``.
Its bytes are, a layer, the K and V rows of prefix and chunk read once at
the K/V heads' width, and each query head's queries in and outputs out, in
the pool's dtype.  Both sides are means, so a launch cut by the trace's edge
moves neither.  The bound that sets the least time is noted, and so is the
scopes' median time by bucket.

The shape is the configuration's: GPT-2's keys (``n_head``, ``n_embd``,
``n_layer``: every layer attends, a K/V head a query head) or LFM2's
(``num_attention_heads``, ``num_key_value_heads``, ``hidden_size``, the
``full_attention`` entries of ``layer_types``).

Nothing to read (None): no device plane, no span store, or a program that
writes no names.  A program that does, with no launch under the scopes or no
`prefill_chunk` span in the traced window, is an error."""
import re

from benchmark import flops, flops_lfm2_moe, xplane_raw
from benchmark.readers import scope_ms
from benchmark.readers.latent_prefill_roofline import _table, traced_chunks


def shape(cfg):
    """(query heads, K/V heads, head size, attention layers) of ``cfg``."""
    if "n_head" in cfg:
        return (cfg["n_head"], cfg["n_head"], cfg["n_embd"] // cfg["n_head"],
                cfg["n_layer"])
    return (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            flops_lfm2_moe.head_dim(cfg), flops_lfm2_moe.attn_layers(cfg))


def chunk_work(cfg, start, tokens, itemsize=2):
    """(FLOPs, bytes) of one chunk's attention, all attention layers."""
    heads, kv_heads, hd, layers = shape(cfg)
    context = tokens * start + tokens * (tokens + 1) / 2.0
    nbytes = layers * ((start + tokens) * 2 * kv_heads * hd
                       + 2 * tokens * heads * hd) * itemsize
    return 4.0 * heads * hd * layers * context, float(nbytes)


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
            raise ValueError("paged_prefill_roofline: %s, in a trace that "
                             "holds the program's other names" % what)
        run.note("paged_prefill_roofline: %s" % what)
        return None
    least, bounds = 0.0, {}
    for start, tokens in chunks:
        t, bound = flops.roofline_seconds(
            *chunk_work(run.cfg, start, tokens), run.peaks)
        least += t
        bounds[bound] = bounds.get(bound, 0) + 1
    least /= len(chunks)
    mean = sum(busy) / len(busy)
    buckets = [int(re.search(r"(\d+)", name[len("jit_"):]).group(1))
               for _, _, name in xplane_raw.programs_of(raw, programs)]
    run.note("paged prefill attention: %d traced launches, %.3f ms each on "
             "the device; %d chunks of %.0f tokens over a prefix of %.0f, "
             "least %.3f ms, bound by %s; by bucket: %s"
             % (len(busy), 1e3 * mean, len(chunks),
                sum(n for _, n in chunks) / len(chunks),
                sum(s for s, _ in chunks) / len(chunks), 1e3 * least,
                max(bounds, key=bounds.get),
                _table([(b, s) for b, s in zip(buckets, seconds) if s > 0])))
    return 100.0 * least / mean
