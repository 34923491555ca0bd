"""Attention-family operators (TPU-era additions to the op set).

The reference predates attention; the long-context mandate of this rebuild
makes it a first-class op family instead of a composed graph of batch_dot +
Softmax (which would materialize the S x S score matrix in HBM).  The op
lowers to the fused Pallas flash kernel on TPU
(`mxnet_tpu/ops/pallas_kernels/flash_attention.py`) and to a blockwise
lax.scan elsewhere; sequence-parallel variants live in
`mxnet_tpu/parallel/sequence.py`.

GSPMD head-axis contract (docs/serving.md "Sharded replicas"): the
serving-side helpers below (`decode_attention`, the paged gathers,
`chunk_attention`, `verify_attention`) are pure jnp gather/einsum over
`(..., embed)` operands with embed laid out HEAD-MAJOR — every
`reshape(b, s, e) -> (b, s, h, hd)` splits the embed axis on heads
first.  A `NamedSharding` that splits embed over n devices where n
divides num_heads therefore maps 1:1 onto a head split: the reshapes
are shard-local, each device attends over its own head group against
its own slice of the K/V pool, and GSPMD partitions every einsum here
without inserting a collective until the output projection's
row-sharded matmul reduces.  Keep it that way — no op in this file may
mix embed positions across the head boundary (e.g. a transpose to
`(hd, h)` order), or sub-mesh serving silently gains all-to-alls.
(`paged_decode_attention` and `paged_chunk_attention` run as Pallas kernels
only in a program built for one device; a sharded replica keeps the gather +
einsum form.)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError
from .registry import OpDef, Param, register
from .pallas_kernels import flash_attention, paged_attention_mod
from .pallas_kernels.flash_attention import flash_attention_bsd


class DotProductAttention(OpDef):
    """Fused scaled-dot-product attention on (batch, heads, seq, head_dim).

    softmax(Q K^T * scale) V without materializing the score matrix.
    ``scale`` defaults to 1/sqrt(head_dim); ``causal=True`` applies a lower
    triangular mask (positions attend to themselves and the past).
    """

    name = "DotProductAttention"
    params = {
        "causal": Param(bool, default=False),
        "scale": Param(float, default=None),
        # <=0 = auto: the kernel layer resolves the measured per-impl
        # winner (512 loop / 1024 streamed / 256 jnp+dS — the round-5
        # on-chip block sweep; see flash_attention._auto_blocks)
        "block_q": Param(int, default=0),
        "block_k": Param(int, default=0),
        # 'bhsd': (batch, heads, seq, head_dim) operands (default).
        # 'bsd': (batch, seq, embed) operands with num_heads — the
        # transposeless TPU path (flash_attention_bsd): no head
        # split/merge transposes are ever built and no layout copies
        # appear at the kernel boundary (round-5 glue attribution).
        "layout": Param(str, default="bhsd"),
        "num_heads": Param(int, default=0),
    }

    def list_arguments(self, params):
        return ["query", "key", "value"]

    def infer_shape(self, params, in_shapes):
        q, k, v = in_shapes
        if k is None and v is not None:
            k = v
        if v is None and k is not None:
            v = k
        if params["layout"] == "bsd":
            if params["num_heads"] < 1:
                raise MXNetError(
                    "DotProductAttention(layout='bsd') requires num_heads")
            for name, s in (("query", q), ("key", k), ("value", v)):
                if s is not None and len(s) != 3:
                    raise MXNetError(
                        "DotProductAttention(layout='bsd'): %s must be "
                        "(batch, seq, embed), got %s" % (name, s))
                if s is not None and s[-1] % params["num_heads"] != 0:
                    raise MXNetError(
                        "DotProductAttention: embed %d not divisible by "
                        "num_heads %d" % (s[-1], params["num_heads"]))
        else:
            for name, s in (("query", q), ("key", k), ("value", v)):
                if s is not None and len(s) != 4:
                    raise MXNetError(
                        "DotProductAttention: %s must be (batch, heads, "
                        "seq, head_dim), got %s" % (name, s))
        if k is not None and v is not None and k != v:
            raise MXNetError(
                "DotProductAttention: key %s and value %s must match"
                % (k, v))
        if q is not None and k is not None and (
                q[0] != k[0] or q[-1] != k[-1] or
                (len(q) == 4 and q[1] != k[1])):
            raise MXNetError(
                "DotProductAttention: query %s and key %s must agree on "
                "(batch, heads, head_dim)" % (q, k))
        out = None
        if q is not None:
            out = tuple(q)
        return [q, k, v], [out], []

    def apply(self, octx, params, inputs, aux):
        q, k, v = inputs
        if params["layout"] == "bsd":
            out = flash_attention_bsd(
                q, k, v, params["num_heads"],
                causal=params["causal"],
                scale=params["scale"],
                block_q=params["block_q"],
                block_k=params["block_k"],
            )
        else:
            out = flash_attention(
                q, k, v,
                causal=params["causal"],
                scale=params["scale"],
                block_q=params["block_q"],
                block_k=params["block_k"],
            )
        # tag for MXNET_BACKWARD_MIRROR_POLICY=attn (save attention
        # outputs, rematerialize everything else — executor._mirror_policy)
        from jax.ad_checkpoint import checkpoint_name
        out = checkpoint_name(out, "attn_out")
        return [out], []


register(DotProductAttention, aliases=("Attention",))


def _kv_head_split(what, e, num_heads, kv_heads):
    """(K/V heads, query heads to a K/V head, head size) of a cache row
    ``e`` wide: grouped-query attention has ``num_heads`` query heads read
    ``kv_heads`` cached ones, query head ``h`` reading K/V head
    ``h // group``; ``kv_heads`` None is the one-to-one form."""
    kv_heads = num_heads if kv_heads is None else int(kv_heads)
    if kv_heads < 1 or num_heads % kv_heads != 0:
        raise MXNetError("%s: num_heads %d is not a multiple of kv_heads %d"
                         % (what, num_heads, kv_heads))
    if e % kv_heads != 0:
        raise MXNetError(
            "%s: embed %d not divisible by num_heads %d"
            % (what, e, kv_heads))
    return kv_heads, num_heads // kv_heads, e // kv_heads


@jax.named_scope("decode_attention")
def decode_attention(q, k_cache, v_cache, pos, num_heads, *, scale=None,
                     kv_heads=None):
    """Single-token attention over a per-sequence K/V cache (serving decode
    step).

    The autoregressive counterpart of `flash_attention`: at decode time the
    query is ONE token per sequence and K/V live in a pre-filled cache, so
    recomputing the (S x S) score matrix per generated token — what running
    the full-sequence kernel every step would do — is O(S^2) work for O(S)
    new information.  This reads the cache once: O(S) per token.

    q:        (batch, embed)        — current-token query projections
    k_cache:  (batch, S_max, embed) — keys,   rows 0..pos[b] valid
    v_cache:  (batch, S_max, embed) — values, rows 0..pos[b] valid
    pos:      (batch,) int          — each row's current position; the
              row's own K/V must already be written at ``pos[b]`` (the
              query attends to itself and the past, matching the training
              kernels' causal mask at that position)
    kv_heads: cached heads, where fewer than ``num_heads`` (grouped-query
              attention): the caches are ``kv_heads * head`` wide, q
              ``num_heads * head``, and query head ``h`` reads K/V head
              ``h // (num_heads // kv_heads)``
    Returns (batch, embed) — q's width; ``num_heads * v_head`` where the V
    cache's heads are narrower than K's (``kv_heads * v_head`` wide).

    Continuous batching gives every row its OWN position, so the validity
    mask is per-row (`j <= pos[b]`), not a shared triangle.  `jax.numpy`
    body: the whole cache is promoted to f32 and read by two einsums, f32
    softmax statistics regardless of cache dtype, like the training
    kernels.  Over a paged pool this is the reference, and the path of
    the pools the kernel does not take (`paged_decode_attention`).
    """
    b, s, e = k_cache.shape
    kv_heads, group, hd = _kv_head_split("decode_attention", e, num_heads,
                                         kv_heads)
    vd = v_cache.shape[2] // kv_heads
    if scale is None:
        scale = 1.0 / float(hd) ** 0.5
    # a K/V head's ``group`` query heads side by side where they differ
    qh = q.reshape((b, num_heads, hd) if group == 1
                   else (b, kv_heads, group, hd))
    kh = k_cache.reshape(b, s, kv_heads, hd)
    valid = (jnp.arange(s, dtype=jnp.int32)[None, :]
             <= pos.astype(jnp.int32)[:, None])  # (b, s)
    # never-attended rows (j > pos) hold stale garbage — zero their V
    # explicitly so a softmax-0 weight multiplies an exact 0, not
    # whatever a freed block left behind (0 * NaN = NaN would otherwise
    # let a stale quantization scale poison a fresh sequence; for
    # finite garbage this is bit-identical to the unguarded product)
    vh = jnp.where(valid[:, :, None, None],
                   v_cache.reshape(b, s, kv_heads, vd).astype(jnp.float32),
                   0.0)
    # scores (b, h, s) in f32: one row of the attention matrix per head
    scores = jnp.einsum(
        "bhd,bshd->bhs" if group == 1 else "bkgd,bskd->bkgs",
        qh.astype(jnp.float32), kh.astype(jnp.float32),
        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(valid[:, None, :] if group == 1
                       else valid[:, None, None, :], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhs,bshd->bhd" if group == 1 else "bkgs,bskd->bkgd",
                     p, vh, preferred_element_type=jnp.float32)
    return out.reshape(b, num_heads * vd).astype(q.dtype)


@jax.named_scope("kv_gather")
def gather_paged_kv(pool, layer, which, block_tables):
    """Materialize per-row K or V context (or, for an int8 pool, its
    dequantization scales) from a paged block pool.

    pool:         (layers, 2, n_blocks, block_size, embed) — the WHOLE K/V
                  pool, or the int8 pool's (layers, 2, n_blocks,
                  block_size) f32 per-row scales, indexed exactly like it
                  (scales travel WITH their block through sharing, CoW,
                  spill and restore); block 0 is the engine's trash block.
    layer, which: the layer, and 0 for K or 1 for V.
    block_tables: (b, m) int32 — row r's table entry t names the pool
                  block holding positions [t*block_size, (t+1)*block_size);
                  unallocated tail entries point at the trash block (their
                  positions are > pos[r], so the decode mask hides them).
    Returns (b, m*block_size, embed), or (b, m*block_size) of scales: the
    contiguous (b, S, embed) layout `decode_attention` reads, reassembled by
    gather — paging changes WHERE rows live, not what attention sees.
    Multiply gathered scales onto the gathered int8 rows
    (``kc.astype(f32) * sc[..., None]``) to dequantize in-graph before the
    attention math — position masking then hides the same tail entries it
    always did, so trash-block scale garbage is never read.

    ONE gather indexes the pool by (layer, which, table entry), so only the
    table's blocks are read.  Taking the layer's K pool out first and
    gathering from that names the same rows, but the TPU compiler
    materialises the slice: a copy of a whole layer's K pool before every
    gather (`slice_bitcast_fusion`; PERF.md, PR 31).

    Tables may ALIAS: with cross-request prefix sharing, several rows of
    one batch can name the same physical block (and the trash block is
    aliased by every padding tail).  A pure gather is read-only, so
    aliasing is safe by construction — each row materializes its own
    copy of the shared rows (tested in tests/test_serve_prefix.py); the
    engine's copy-on-write guarantees no WRITE ever targets a block two
    tables share.
    """
    b, m = block_tables.shape
    ctx = pool[layer, which, block_tables.astype(jnp.int32)]
    return ctx.reshape((b, m * pool.shape[3]) + pool.shape[4:])


def paged_decode_kernel_applies(pool, num_heads, kv_heads=None):
    """Whether `paged_decode_attention` over ``pool`` (an unquantised
    ``(layers, 2, n_blocks, block_size, embed)`` pool of ``kv_heads``
    heads a row, ``num_heads`` where None; or the pair of K's and V's
    arrays where their heads differ in width,
    `pallas_kernels.paged_attention.pair`) runs as the Pallas kernel, from
    what the code can see where the program is traced: the kernel's own
    conditions (`pallas_kernels.paged_attention.applies`: a TPU backend or
    the interpreter, f32 or bf16 blocks of whole tiles, a q/k and a v head
    width it takes: V heads of 64 or 128, K heads as wide or, beside
    narrower V heads, 192) and a program built for one device.  A
    tensor-sharded engine's program is partitioned by GSPMD, which refuses
    Mosaic kernels (`pallas_kernels/_spmd.py`); it keeps the `jax.numpy`
    body."""
    from ..parallel.mesh import get_mesh

    mesh = get_mesh()
    return (mesh is None or mesh.size == 1) \
        and paged_attention_mod.applies(
            pool, num_heads if kv_heads is None else kv_heads)


def paged_decode_attention(q, pool, layer, block_tables, pos, num_heads,
                           *, scale=None, kv_heads=None):
    """`decode_attention` of every row over layer ``layer`` of a paged K/V
    pool ``(layers, 2, n_blocks, block_size, embed)``; with ``kv_heads``
    fewer than ``num_heads`` the pool's rows are ``kv_heads`` heads wide and
    q ``num_heads`` (grouped-query attention).

    Where `paged_decode_kernel_applies` holds, the Pallas kernel
    `paged_decode_attn` walks each row's live blocks in the pool's own
    dtype with the pool left whole in HBM, under the scope
    `decode_attention`.  Everywhere else (the CPU backend, a sharded
    engine, widths the kernel does not take) the `jax.numpy` body runs:
    each row's blocks gathered from the whole pool by (layer, K or V,
    table entry) (`gather_paged_kv`), then `decode_attention` over the
    table-wide context.  That body is the kernel's reference in the tests:
    the two agree up to the order of the float32 sums (masked tail
    positions contribute exact zeros either way).

    Dead-row contract (megastep decode): a retired/padding row is fed
    ``pos = n_table * block_size`` — the first position PAST its table
    coverage — so its K/V write redirects to the trash block (entry
    index ``pos // bs == n_table`` maps to block 0) and its validity
    mask here goes all-valid over whatever the table's blocks hold.
    That output is garbage by construction and is discarded in-graph
    (the scan emits the ``-2`` dead sentinel instead); it cannot
    contaminate live rows because every row's softmax is independent."""
    if paged_decode_kernel_applies(pool, num_heads, kv_heads):
        with jax.named_scope("decode_attention"):
            return paged_attention_mod.paged_decode_attn(
                q, pool, layer, block_tables, pos, num_heads, scale=scale,
                kv_heads=kv_heads)
    k_pool, v_pool, v_at = paged_attention_mod.pair(pool)
    kc = gather_paged_kv(k_pool, layer, 0, block_tables)
    vc = gather_paged_kv(v_pool, layer, v_at, block_tables)
    return decode_attention(q, kc, vc, pos, num_heads, scale=scale,
                            kv_heads=kv_heads)


def paged_chunk_kernel_applies(pool, num_heads, kv_heads, c, n_table):
    """Whether `paged_chunk_attention` of a chunk of ``c`` queries over
    tables of ``n_table`` entries of ``pool`` (an unquantised ``(layers, 2,
    n_blocks, block_size, embed)`` pool of ``kv_heads`` heads a row,
    ``num_heads`` where None) runs as the Pallas kernel, from what the code
    can see where the program is traced: the kernel's own conditions
    (`pallas_kernels.paged_attention.prefill_applies`: those of
    `paged_decode_kernel_applies` with a q/k and a v head alike, 64 or 128
    wide, in one array, a chunk of whole sublane tiles, and a table whose K
    and V fit the kernel's VMEM) and a program built for one device.  A pair
    of K and V arrays of their own widths is never the kernel's: its chunks
    are walked by `paged_chunk_stream`."""
    from ..parallel.mesh import get_mesh

    mesh = get_mesh()
    return (mesh is None or mesh.size == 1) \
        and paged_attention_mod.prefill_applies(
            pool, num_heads if kv_heads is None else kv_heads, c, n_table)


def paged_chunk_attention(q, pool, layer, block_tables, start, num_heads, *,
                          scale=None, kv_heads=None):
    """`chunk_attention` of a chunk of queries a row over layer ``layer`` of
    a paged K/V pool ``(layers, 2, n_blocks, block_size, embed)`` whose
    blocks already hold the chunk's own rows; ``kv_heads`` as in
    `paged_decode_attention`.

    Where `paged_chunk_kernel_applies` holds, the Pallas kernel
    `paged_prefill_attn` walks each row's live blocks in the pool's own
    dtype with the pool left whole in HBM, under the scope
    `chunk_attention`.  A pair of K and V arrays of their own widths
    (`pallas_kernels.paged_attention.pair`) is walked by
    `paged_chunk_stream`, a step of the table at a time.  Everywhere else
    (the CPU backend, a sharded engine, widths the kernel does not take) the
    `jax.numpy` body runs: the table's blocks gathered (`gather_paged_kv`),
    then `chunk_attention` over the table-wide context, the kernel's
    reference in the tests."""
    if paged_chunk_kernel_applies(pool, num_heads, kv_heads, q.shape[1],
                                  block_tables.shape[1]):
        with jax.named_scope("chunk_attention"):
            return paged_attention_mod.paged_prefill_attn(
                q, pool, layer, block_tables, start, num_heads, scale=scale,
                kv_heads=kv_heads)
    if isinstance(pool, (tuple, list)):
        return paged_chunk_stream(q, pool, layer, block_tables, start,
                                  num_heads, scale=scale, kv_heads=kv_heads)
    kc = gather_paged_kv(pool, layer, 0, block_tables)
    vc = gather_paged_kv(pool, layer, 1, block_tables)
    return chunk_attention(q, kc, vc, start, num_heads, scale=scale,
                           kv_heads=kv_heads)


# positions of the table one step of `paged_chunk_stream` attends
_STREAM_STEP_TOKENS = 1024


@jax.named_scope("chunk_attention")
def paged_chunk_stream(q, pool, layer, block_tables, start, num_heads, *,
                       scale=None, kv_heads=None):
    """`chunk_attention` of a chunk of queries a row over layer ``layer`` of
    a paged pool whose blocks already hold the chunk's own rows, walked
    `_STREAM_STEP_TOKENS` positions of the table at a step: each step gathers its
    blocks' K and V (in the pool's dtype, at their own widths: ``pool`` may
    be a `pallas_kernels.paged_attention.pair`), scores the chunk against
    them in float32 and folds them into a running maximum, sum and
    accumulator (the online softmax of the flash kernels; the probabilities
    rounded to the pool's dtype for the product with V).  The loop's trip
    count is the steps the rows' chunks reach, a value and not a shape, so a
    chunk at the head of a long table walks one step; no value holds scores
    wider than a step, whatever the table's width.  Positions past a row's
    chunk score -inf and their V rows are zeroed, as in `chunk_attention`.
    Returns (b, c, num_heads * v_head) in q's dtype."""
    k_pool, v_pool, v_at = paged_attention_mod.pair(pool)
    b, c, _ = q.shape
    m = block_tables.shape[1]
    bs = k_pool.shape[3]
    kv_heads, group, hd = _kv_head_split("paged_chunk_stream",
                                         k_pool.shape[4], num_heads,
                                         kv_heads)
    vd = v_pool.shape[4] // kv_heads
    if scale is None:
        scale = 1.0 / float(hd) ** 0.5
    step_blocks = max(1, min(_STREAM_STEP_TOKENS // bs, m))
    t = step_blocks * bs
    n_steps = -(-m // step_blocks)
    # entries past the table's width name the trash block, and are masked
    tables = jnp.pad(block_tables.astype(jnp.int32),
                     ((0, 0), (0, n_steps * step_blocks - m)))
    start = start.astype(jnp.int32)
    qpos = start[:, None] + jnp.arange(c, dtype=jnp.int32)[None]  # (b, c)
    # the last position each row's chunk wrote, inside the table
    last = jnp.minimum(start + c, m * bs) - 1
    dt = k_pool.dtype
    qh = q.reshape(b, c, kv_heads, group, hd).astype(dt)

    def step(i, carry):
        mx, l, acc = carry
        ent = lax.dynamic_slice_in_dim(tables, i * step_blocks, step_blocks,
                                       axis=1)
        kc = k_pool[layer, 0, ent].reshape(b, t, kv_heads, hd)
        vc = v_pool[layer, v_at, ent].reshape(b, t, kv_heads, vd)
        j = i * t + jnp.arange(t, dtype=jnp.int32)
        vc = jnp.where((j[None] <= last[:, None])[:, :, None, None], vc,
                       jnp.zeros((), dt))
        s = jnp.einsum("bckgd,btkd->bkgct", qh, kc,
                       preferred_element_type=jnp.float32) * scale
        seen = j[None, None, :] <= qpos[:, :, None]              # (b, c, t)
        s = jnp.where(seen[:, None, None], s, -jnp.inf)
        m_new = jnp.maximum(mx, jnp.max(s, axis=-1))
        alpha = jnp.exp(mx - m_new)
        p = jnp.exp(s - m_new[..., None])
        l = alpha * l + jnp.sum(p, axis=-1)
        acc = alpha[..., None] * acc + jnp.einsum(
            "bkgct,btkd->bkgcd", p.astype(dt), vc,
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    stats = (b, kv_heads, group, c)
    _, l, acc = lax.fori_loop(
        0, jnp.max(last) // t + 1, step,
        (jnp.full(stats, -jnp.inf, jnp.float32),
         jnp.zeros(stats, jnp.float32),
         jnp.zeros(stats + (vd,), jnp.float32)))
    out = (acc / l[..., None]).transpose(0, 3, 1, 2, 4)
    return out.reshape(b, c, num_heads * vd).astype(q.dtype)


@jax.named_scope("chunk_attention")
def chunk_attention(q, k_cache, v_cache, start, num_heads, *, scale=None,
                    kv_heads=None):
    """Chunked-prefill attention: a c-token query chunk at absolute
    positions ``start .. start+c-1`` attends to the cached prefix plus
    itself (causal within the chunk).

    The generalization between the two existing programs: c=1 degenerates
    to `decode_attention` (one query over the cache) and start=0 with
    c=S degenerates to the full causal forward.  Chunked prefill streams
    a long prompt through the cache bucket-sized chunks at a time, so a
    prompt longer than the largest prefill bucket needs no dedicated
    compiled shape — each chunk is a fixed (1, c) program.

    q:        (b, c, embed)   — query projections of the chunk
    k_cache:  (b, S, embed)   — keys, the chunk's own rows already written
    v_cache:  (b, S, embed)
    start:    (b,) int        — absolute position of each row's chunk
    kv_heads: cached heads where fewer than ``num_heads`` (grouped-query
              attention, as in `decode_attention`): the caches are then
              ``kv_heads * head`` wide and q ``num_heads * head``
    Returns (b, c, embed) — q's width, or ``num_heads * v_head`` where the
    V cache's heads are narrower than K's.  f32 softmax statistics like the
    siblings.
    """
    b, c, e = q.shape
    s = k_cache.shape[1]
    kv_heads, group, hd = _kv_head_split("chunk_attention",
                                         k_cache.shape[2], num_heads,
                                         kv_heads)
    vd = v_cache.shape[2] // kv_heads
    if scale is None:
        scale = 1.0 / float(hd) ** 0.5
    # a K/V head's ``group`` query heads side by side where they differ
    qh = q.reshape((b, c, num_heads, hd) if group == 1
                   else (b, c, kv_heads, group, hd))
    kh = k_cache.reshape(b, s, kv_heads, hd)
    start = start.astype(jnp.int32)
    # rows past the chunk's own last position (j >= start+c) are stale
    # garbage no query attends: zero their V explicitly so a softmax-0
    # weight multiplies an exact 0 (0 * NaN from a freed block's stale
    # quantization scale would otherwise poison the output; for finite
    # garbage this is bit-identical to the unguarded product)
    written = (jnp.arange(s, dtype=jnp.int32)[None, :]
               < (start + c)[:, None])                   # (b, s)
    vh = jnp.where(written[:, :, None, None],
                   v_cache.reshape(b, s, kv_heads, vd).astype(jnp.float32),
                   0.0)
    scores = jnp.einsum(
        "bchd,bshd->bhcs" if group == 1 else "bckgd,bskd->bkgcs",
        qh.astype(jnp.float32), kh.astype(jnp.float32),
        preferred_element_type=jnp.float32) * scale
    # query i (absolute position start+i) sees cache rows j <= start+i
    qpos = start[:, None] + \
        jnp.arange(c, dtype=jnp.int32)[None, :]          # (b, c)
    valid = (jnp.arange(s, dtype=jnp.int32)[None, None, :]
             <= qpos[:, :, None])                        # (b, c, s)
    scores = jnp.where(valid[:, None] if group == 1
                       else valid[:, None, None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhcs,bshd->bchd" if group == 1
                     else "bkgcs,bskd->bckgd", p, vh,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, c, num_heads * vd).astype(q.dtype)


@jax.named_scope("verify_attention")
def verify_attention(q, k_cache, v_cache, start, length, num_heads, *,
                     scale=None):
    """Length-masked multi-query verify attention (speculative decoding).

    The draft-verify generalization of `chunk_attention`: a c-token
    query chunk at absolute positions ``start .. start+c-1`` attends to
    the cached prefix plus itself causally, but only the first
    ``length[b]`` chunk tokens of each row are REAL — chunk keys at
    offsets >= length are masked for every query (padding rows, or
    speculative positions clipped at the cache end), with each query's
    own position kept visible so fully-masked queries stay finite
    (their outputs are don't-cares the engine never emits).

    ``length == c`` reproduces `chunk_attention` bit-for-bit (every
    real query already attends only keys <= its own position, all of
    which are real), so the speculative verify step and chunked prefill
    share one masking contract; c=1 with length=1 degenerates to
    `decode_attention` — one launch scores a whole draft run with the
    numerics single-token decode would have produced.

    q:        (b, c, embed)  — query projections of the fed chunk
              (row's last emitted token + its k draft proposals)
    k_cache:  (b, S, embed)  — keys, the chunk's own rows already
              scattered in by the caller
    v_cache:  (b, S, embed)
    start:    (b,) int       — absolute position of each row's chunk
    length:   (b,) int       — real fed tokens per row (1 <= length <= c)
    Returns (b, c, embed).  f32 softmax statistics like the siblings.
    """
    b, c, e = q.shape
    s = k_cache.shape[1]
    if e % num_heads != 0:
        raise MXNetError(
            "verify_attention: embed %d not divisible by num_heads %d"
            % (e, num_heads))
    hd = e // num_heads
    if scale is None:
        scale = 1.0 / float(hd) ** 0.5
    qh = q.reshape(b, c, num_heads, hd)
    kh = k_cache.reshape(b, s, num_heads, hd)
    start = start.astype(jnp.int32)
    # rows past the fed span (j >= start+c) are stale garbage no query
    # attends (the span itself was scattered fresh by this launch):
    # zero their V so softmax-0 weights multiply exact 0s — same stale-
    # scale NaN guard as `chunk_attention`, bit-identical on finite data
    written = (jnp.arange(s, dtype=jnp.int32)[None, :]
               < (start + c)[:, None])                   # (b, s)
    vh = jnp.where(written[:, :, None, None],
                   v_cache.reshape(b, s, num_heads, hd).astype(jnp.float32),
                   0.0)
    scores = jnp.einsum(
        "bchd,bshd->bhcs", qh.astype(jnp.float32), kh.astype(jnp.float32),
        preferred_element_type=jnp.float32) * scale
    qpos = start[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]  # (b, c)
    j = jnp.arange(s, dtype=jnp.int32)[None, None, :]
    causal = j <= qpos[:, :, None]                       # (b, c, s)
    # chunk keys past each row's real length are garbage; a query's own
    # position stays visible so out-of-length queries keep a finite
    # softmax (their outputs are discarded, never attended again)
    real = (j < (start + length.astype(jnp.int32))[:, None, None]) | \
        (j == qpos[:, :, None])
    scores = jnp.where((causal & real)[:, None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhcs,bshd->bchd", p, vh,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, c, e).astype(q.dtype)


class DecodeAttention(OpDef):
    """Symbol-level wrapper of `decode_attention` so KV-cache decode graphs
    can be expressed with the op registry (query (batch, embed), caches
    (batch, S_max, embed), pos (batch,))."""

    name = "DecodeAttention"
    params = {
        "num_heads": Param(int, required=True),
        "scale": Param(float, default=None),
    }

    def list_arguments(self, params):
        return ["query", "key_cache", "value_cache", "pos"]

    def infer_shape(self, params, in_shapes):
        q, kc, vc, pos = in_shapes
        if kc is None and vc is not None:
            kc = vc
        if vc is None and kc is not None:
            vc = kc
        for name, shp, rank in (("query", q, 2), ("key_cache", kc, 3),
                                ("value_cache", vc, 3), ("pos", pos, 1)):
            if shp is not None and len(shp) != rank:
                raise MXNetError(
                    "DecodeAttention: %s must be rank %d, got %s"
                    % (name, rank, shp))
        if kc is not None and vc is not None and kc != vc:
            raise MXNetError(
                "DecodeAttention: key_cache %s and value_cache %s must "
                "match" % (kc, vc))
        if q is not None and kc is not None and (
                q[0] != kc[0] or q[-1] != kc[-1]):
            raise MXNetError(
                "DecodeAttention: query %s and key_cache %s must agree on "
                "(batch, embed)" % (q, kc))
        out = tuple(q) if q is not None else None
        if q is not None and pos is None:
            pos = (q[0],)
        return [q, kc, vc, pos], [out], []

    def apply(self, octx, params, inputs, aux):
        q, kc, vc, pos = inputs
        out = decode_attention(q, kc, vc, pos.astype(jnp.int32),
                               params["num_heads"], scale=params["scale"])
        return [out], []


register(DecodeAttention)


class LayerNorm(OpDef):
    """Layer normalization over the last axis (transformer-era counterpart
    of `src/operator/batch_norm-inl.h`; no running stats, so it is SPMD- and
    scan-friendly)."""

    name = "LayerNorm"
    params = {"eps": Param(float, default=1e-5)}

    def list_arguments(self, params):
        return ["data", "gamma", "beta"]

    def infer_shape(self, params, in_shapes):
        d = in_shapes[0]
        if d is None:
            return in_shapes, [None], []
        c = (d[-1],)
        return [d, c, c], [d], []

    def apply(self, octx, params, inputs, aux):
        from .pallas_kernels.layer_norm import layer_norm

        x, gamma, beta = inputs
        return [layer_norm(x, gamma, beta, params["eps"])], []


register(LayerNorm)
