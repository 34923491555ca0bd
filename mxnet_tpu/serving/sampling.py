"""In-graph token sampling for the serving decode/prefill programs.

The engine's compiled programs have FIXED shapes (the zero-retrace
contract), so sampling configuration cannot branch the program: every
knob is a per-row ARRAY argument and every mode runs through one traced
body.  Greedy is temperature <= 0 (the argmax path, bit-identical to the
PR-7 greedy programs); temperature / top-k / top-p compose the standard
way (scale, then k-mask, then nucleus-mask, then categorical draw).

Determinism is request-keyed, not batch-keyed: the draw for the token
that will occupy absolute position P of request R uses
``fold_in(PRNGKey(seed_R), P)``.  Consequences the tests pin down:

* the same (seed, prompt) replays the same generation, process-wide;
* batch composition is invisible — a request samples the same tokens
  alone or surrounded by neighbours joining/leaving mid-flight (the
  continuous-batching parity contract extends to sampled traffic);
* a preempted-and-requeued sequence resumes drawing exactly where it
  left off (position-keyed, not step-keyed);
* the megastep decode scan is bit-identical to m sequential launches —
  each fused step folds in the CARRIED position, so the fused program
  consumes exactly the RNG stream the single-step loop would.

Padding rows ride the greedy path (temperature 0) and their output is
discarded by the scheduler.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["sample_tokens"]


def _mask_top_k_top_p(scaled, top_k, top_p):
    """Compose the top-k and nucleus masks off ONE descending sort (this
    runs in every sampling-program decode step — a second full-vocab
    sort would be pure waste: masking to -inf only moves entries to the
    tail the first sort already built).  Top-k keeps the k largest
    (k <= 0 disables); top-p then keeps the smallest prefix of the
    remaining descending-prob mass reaching p (the top token always
    survives; p >= 1 disables).  Ties at either threshold are all kept
    — the usual caveat."""
    v = scaled.shape[-1]
    desc = jnp.flip(jnp.sort(scaled, axis=-1), axis=-1)        # (b, V)
    k = jnp.clip(top_k.astype(jnp.int32), 0, v)
    k_eff = jnp.where(k > 0, k, v)[:, None]
    # top-k applied in sorted space: positions >= k drop out
    desc_k = jnp.where(jnp.arange(v, dtype=jnp.int32)[None, :] < k_eff,
                       desc, -jnp.inf)
    probs = jax.nn.softmax(desc_k, axis=-1)
    csum = jnp.cumsum(probs, axis=-1)
    p_eff = jnp.clip(top_p.astype(jnp.float32), 0.0, 1.0)[:, None]
    keep = (csum - probs) < p_eff          # mass BEFORE the token < p
    # a k-masked tail entry must NEVER survive into `keep`: its prob is
    # exactly 0, so its mass-before is the TOTAL mass — whether that
    # compares < 1.0 is float-rounding luck (a partitioned cumsum on a
    # sub-mesh replica rounds differently than one device), and one tail
    # survivor makes thr = -inf, silently disabling top-k entirely
    keep = keep & jnp.isfinite(desc_k)
    # the smallest surviving logit bounds both filters (it lives inside
    # the top-k prefix, so scaled >= thr implies the k-mask too)
    thr = jnp.min(jnp.where(keep, desc_k, jnp.inf), axis=-1, keepdims=True)
    return jnp.where(scaled >= thr, scaled, -jnp.inf)


@jax.named_scope("sampler")
def sample_tokens(logits, temperature, top_k, top_p, seed, newpos):
    """One token per row from per-row sampling params, inside the
    compiled program.

    logits:      (b, V)
    temperature: (b,) f32 — <= 0 selects greedy argmax for the row
    top_k:       (b,) int32 — <= 0 disables
    top_p:       (b,) f32 — >= 1 disables
    seed:        (b,) uint32 — the request's RNG identity
    newpos:      (b,) int32 — the absolute position the sampled token
                 will occupy (prefill: prompt length; decode: pos + 1)
    Returns (b,) int32 token ids.
    """
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    t = jnp.where(temperature > 0, temperature, 1.0).astype(jnp.float32)
    scaled = logits / t[:, None]
    masked = _mask_top_k_top_p(scaled, top_k, top_p)

    def draw(seed_i, pos_i, row):
        key = jax.random.fold_in(jax.random.PRNGKey(seed_i), pos_i)
        return jax.random.categorical(key, row)

    sampled = jax.vmap(draw)(seed.astype(jnp.uint32),
                             newpos.astype(jnp.int32),
                             masked).astype(jnp.int32)
    return jnp.where(temperature > 0, sampled, greedy)
