"""One chip's share of a sparse expert layer (serving).

The layer of the DeepSeek-V3 block and of its relatives: a router over ALL
``n_routed`` experts (sigmoid scores, a per-expert selection bias that
chooses but does not weigh, the ``top_k`` largest, weights renormalised and
scaled), the routed experts' SwiGLU FFNs and, where the model has one, a
shared expert every token passes through.  A wide expert-parallel deployment
gives each chip a contiguous range of the routed experts; this module
computes what ONE such chip adds:

    y = sum over the token's chosen experts e with lo <= e < hi of
        w_e * E_e(u)     [+ E_shared(u)]

and leaves out what the experts held elsewhere would add (their terms
arrive by the deployment's exchange, which a one-chip program does not
have).  With ``experts_held = (0, n_routed)`` it is the whole layer.

Dispatch is dropless and follows the routed rows.  The (row, choice) pairs
that fall on held experts are sorted by expert; each expert's rows are cut
into tiles of up to `_TILE` rows, and a `lax.fori_loop` whose trip count is the
number of tiles that hold a row (a value, not a shape) runs one tile a
step: gather the tile's rows, the expert's three matrices by index, SwiGLU,
and add ``w * E_e(u)`` into the rows' float32 accumulator.  So an expert no
row chose costs no step and no weight traffic, and a launch of 64 rows, of
which a 32nd of the pairs land here, reads two or three experts a layer and
not twelve.  `jax.lax.ragged_dot` takes the rows as a SHAPE: dropless, that
is ``rows * top_k`` of them (every choice of every row may be held), 32
times what lands here in expectation, so it is not used.

Batch invariance: a row's pairs are added in ascending expert order into a
zero accumulator, each product computed from that row alone, so its result
does not depend on what else is in the batch (tests/test_latent_moe.py).

Scopes, for the profiler's trace: ``moe_router``, ``moe_dispatch`` (the sort
and each tile's row gather), ``moe_experts`` (the tile's three products),
``moe_combine`` (the weighted add), ``moe_shared``; ``moe_loop`` is the loop
operation around the tiles, whose steps carry the three before it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


#: rows of one expert run in one step of the loop: at this width a step's
#: products (128 x 44 M multiply-adds) take about as long as reading the
#: expert's three matrices, so a fuller expert costs steps, not idle units
_TILE = 128


def swiglu(x, w_gate, w_up, w_down):
    """``(silu(x @ w_gate.T) * (x @ w_up.T)) @ w_down.T`` with weights stored
    (out, in) like the repo's other projections; float32 sums, results in
    ``x``'s dtype."""
    g = jnp.dot(x, w_gate.T, preferred_element_type=jnp.float32)
    u = jnp.dot(x, w_up.T, preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    return jnp.dot(h, w_down.T,
                   preferred_element_type=jnp.float32).astype(x.dtype)


@jax.named_scope("moe_router")
def route(u, w_router, bias, top_k, scale, eps=1e-20):
    """(chosen experts (n, top_k) int32, their weights (n, top_k) float32).

    ``sc = sigmoid(u W_g)`` in float32, as the published gate computes it
    (`Precision.HIGHEST`: a near-tie must not flip between the program and
    its reference); the ``top_k`` largest of ``sc + bias`` are chosen,
    their weights are ``sc`` WITHOUT the bias, renormalised to sum to one
    (``w / (sum(w) + eps)``: the published gates differ in ``eps`` alone,
    1e-20 in DeepSeek-V3's and 1e-6 in LFM2's) and multiplied by ``scale``
    (`routed_scaling_factor`)."""
    sc = jax.nn.sigmoid(jnp.dot(
        u.astype(jnp.float32), w_router.astype(jnp.float32).T,
        precision=lax.Precision.HIGHEST))
    _, idx = lax.top_k(sc + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(sc, idx, axis=1)
    w = w / (jnp.sum(w, axis=1, keepdims=True) + eps)
    return idx.astype(jnp.int32), w * scale


def held_share(u, idx, w, w_gate, w_up, w_down, experts_held, valid=None):
    """The held experts' part of the routed sum, ``(n, d)`` float32, and the
    rows each held expert was given, ``(hi - lo,)`` int32.

    u:       (n, d) rows
    idx, w:  `route`'s choices and weights
    w_gate, w_up: (hi - lo, d, f); w_down: (hi - lo, f, d), experts stacked
             on axis 0 (in, out), as `TransformerKVModel`'s banks are
    valid:   (n,) bool or None: rows that are padding are routed nowhere,
             so they count in no expert's load and touch no weight
    """
    n, d = u.shape
    lo, hi = experts_held
    n_held = hi - lo
    top_k = idx.shape[1]
    pairs = n * top_k
    tile = min(_TILE, -(-n // 8) * 8)
    with jax.named_scope("moe_dispatch"):
        held = (idx >= lo) & (idx < hi)
        if valid is not None:
            held = held & valid[:, None]
        # the expert's index among those held here; pairs that fall
        # elsewhere sort last
        local = jnp.where(held, idx - lo, n_held).reshape(pairs)
        order = jnp.argsort(local, stable=True).astype(jnp.int32)
        counts = jnp.sum(
            local[:, None] == jnp.arange(n_held, dtype=jnp.int32)[None],
            axis=0, dtype=jnp.int32)
        first = jnp.cumsum(counts) - counts          # each group's start
        tiles = (counts + tile - 1) // tile
        tiles_end = jnp.cumsum(tiles)
        flat_w = w.reshape(pairs)

    def body(t, acc):
        with jax.named_scope("moe_dispatch"):
            e = jnp.minimum(jnp.sum(t >= tiles_end, dtype=jnp.int32),
                            n_held - 1)
            k = t - (tiles_end[e] - tiles[e])        # tile within the group
            at = first[e] + k * tile + jnp.arange(tile, dtype=jnp.int32)
            live = at < first[e] + counts[e]
            pair = order[jnp.minimum(at, pairs - 1)]
            row = pair // top_k
            x = jnp.take(u, row, axis=0)             # (tile, d)
        with jax.named_scope("moe_experts"):
            g = jnp.dot(x, lax.dynamic_index_in_dim(w_gate, e, 0, False),
                        preferred_element_type=jnp.float32)
            up = jnp.dot(x, lax.dynamic_index_in_dim(w_up, e, 0, False),
                         preferred_element_type=jnp.float32)
            h = (jax.nn.silu(g) * up).astype(u.dtype)
            y = jnp.dot(h, lax.dynamic_index_in_dim(w_down, e, 0, False),
                        preferred_element_type=jnp.float32)
        with jax.named_scope("moe_combine"):
            # dead rows of a tile add to the spare row n, with weight 0
            y = y * jnp.where(live, flat_w[pair], 0.0)[:, None]
            return acc.at[jnp.where(live, row, n)].add(y)

    # the loop operation itself under a scope of its own: a device trace
    # holds it as one event around its steps' operations, and under no scope
    # at all it would read as time the program gave no name
    with jax.named_scope("moe_loop"):
        acc = lax.fori_loop(0, tiles_end[-1], body,
                            jnp.zeros((n + 1, d), jnp.float32))
    return acc[:n], counts


def expert_layer(u, w_router, bias, experts, shared=None, *, top_k, scale,
                 experts_held, valid=None, eps=None):
    """This chip's share of the layer's output for rows ``u`` (n, d), in
    ``u``'s dtype, and the held experts' row counts.

    experts: (w_gate, w_up, w_down) banks of the held experts
    shared:  (w_gate, w_up, w_down) of the shared expert, (out, in), or
             None for a layer that has none
    eps:     `route`'s, in the weights' renormalisation (None: its own)
    """
    idx, w = route(u, w_router, bias, top_k, scale) if eps is None \
        else route(u, w_router, bias, top_k, scale, eps)
    routed, counts = held_share(u, idx, w, *experts,
                                experts_held=experts_held, valid=valid)
    if shared is None:
        with jax.named_scope("moe_combine"):
            return routed.astype(u.dtype), counts
    with jax.named_scope("moe_shared"):
        common = swiglu(u, *shared)
    with jax.named_scope("moe_combine"):
        return (routed + common.astype(jnp.float32)).astype(u.dtype), counts
