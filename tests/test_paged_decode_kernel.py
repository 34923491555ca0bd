"""The paged decode attention kernel against its `jax.numpy` reference.

The kernel body (`ops/pallas_kernels/paged_attention.py`) runs through the
Pallas interpreter on the CPU; the reference is what every pool the kernel
does not take still runs: `decode_attention` over `gather_paged_kv` of the
layer's K and V blocks.  One test per contract of the kernel, one case per
shape, so that each counts.  (That the kernel lowers for the chip at the
cell's widths is `tests/test_aot_compile.py`'s.)
"""
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import tracing
from mxnet_tpu.ops.attention import (decode_attention, gather_paged_kv,
                                     paged_decode_attention,
                                     paged_decode_kernel_applies)
from mxnet_tpu.ops.pallas_kernels import paged_attention_mod as pa
from mxnet_tpu.serving import ServingEngine, TransformerKVModel

LAYERS, LAYER = 2, 1


@pytest.fixture
def interpreted(monkeypatch):
    """The kernel's gate sees the interpreter; two blocks to a chunk, so
    that short tables still walk several chunks and both buffer slots."""
    monkeypatch.setattr(pa, "_INTERPRET", True)
    monkeypatch.setattr(pa, "_CHUNK_TOKENS", 32)


def _pool(rs, n_blocks, bs, embed, dtype):
    return jnp.asarray(rs.randn(LAYERS, 2, n_blocks, bs, embed), dtype)


def _tables(rs, b, m, n_blocks):
    """Every row its own blocks, none the trash block."""
    own = 1 + rs.permutation(n_blocks - 1)[:b * m]
    return jnp.asarray(own.reshape(b, m), jnp.int32)


def _reference(q, pool, tables, pos, heads):
    return decode_attention(q, gather_paged_kv(pool, LAYER, 0, tables),
                            gather_paged_kv(pool, LAYER, 1, tables), pos,
                            heads)


def _kernel(q, pool, tables, pos, heads):
    assert paged_decode_kernel_applies(pool, heads)
    out = jax.jit(lambda *a: paged_decode_attention(a[0], a[1], LAYER, a[2],
                                                    a[3], heads))(
        q, pool, tables, pos)
    assert out.shape == q.shape and out.dtype == q.dtype
    return np.asarray(out, np.float32)


def _agree(got, q, pool, tables, pos, heads):
    want = np.asarray(_reference(q, pool, tables, pos, heads), np.float32)
    # the same products summed in another order, then rounded to q's dtype
    tol = 2e-5 if q.dtype == jnp.float32 else 1.6e-2
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


def _case(seed, b, m, bs, heads, head_dim, dtype, n_blocks=None):
    rs = np.random.RandomState(seed)
    n_blocks = n_blocks or b * m + 1
    embed = heads * head_dim
    return (rs, jnp.asarray(rs.randn(b, embed), dtype),
            _pool(rs, n_blocks, bs, embed, dtype),
            _tables(rs, b, m, n_blocks))


def test_gate_is_closed_on_the_cpu_backend_and_for_other_widths(monkeypatch):
    pool = jnp.zeros((1, 2, 4, 16, 128), jnp.bfloat16)
    assert not paged_decode_kernel_applies(pool, 2)       # no TPU here
    monkeypatch.setattr(pa, "_INTERPRET", True)
    assert paged_decode_kernel_applies(pool, 2)           # 2 heads of 64
    assert paged_decode_kernel_applies(pool, 1)           # 1 head of 128
    assert not paged_decode_kernel_applies(pool, 4)       # heads of 32
    assert not paged_decode_kernel_applies(pool.astype(jnp.int8), 2)
    assert not paged_decode_kernel_applies(pool[:, :, :, :8], 2)  # half tiles
    assert paged_decode_kernel_applies(
        pool[:, :, :, :8].astype(jnp.float32), 2)
    assert not paged_decode_kernel_applies(
        jnp.zeros((1, 2, 4, 16, 192), jnp.bfloat16), 3)   # 1.5 lane tiles


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_ragged_positions_across_a_batch(interpreted, dtype):
    """Every row its own depth: the first position, the last of a block,
    the first of the next, a chunk's edge, the table's last position."""
    _, q, pool, tables = _case(0, 6, 6, 16, 2, 64, dtype)
    pos = jnp.asarray([0, 15, 16, 31, 32, 95], jnp.int32)
    _agree(_kernel(q, pool, tables, pos, 2), q, pool, tables, pos, 2)


def test_padding_rows_walk_the_trash_block(interpreted):
    """The engine pads a launch with rows at position 0 whose tables name
    only the trash block; they lie before, between and after live rows."""
    _, q, pool, tables = _case(1, 5, 4, 16, 2, 64, jnp.bfloat16)
    live = jnp.asarray([False, True, False, True, False])
    tables = jnp.where(live[:, None], tables, 0)
    pos = jnp.where(live, jnp.asarray([0, 40, 0, 63, 0]), 0).astype(jnp.int32)
    got = _kernel(q, pool, tables, pos, 2)
    _agree(got, q, pool, tables, pos, 2)
    # a padding row attends to the trash block's first row alone
    np.testing.assert_array_equal(
        got[0], np.asarray(pool[LAYER, 1, 0, 0], np.float32))


def test_aliased_tables_read_the_same_blocks(interpreted):
    """Prefix sharing: two rows name the same physical blocks for their
    common prefix, and a third names them all."""
    _, q, pool, tables = _case(2, 3, 4, 16, 2, 64, jnp.bfloat16)
    tables = tables.at[1, :2].set(tables[0, :2]).at[2].set(tables[0])
    pos = jnp.asarray([50, 37, 50], jnp.int32)
    got = _kernel(q.at[2].set(q[0]), pool, tables, pos, 2)
    _agree(got, q.at[2].set(q[0]), pool, tables, pos, 2)
    np.testing.assert_array_equal(got[0], got[2])


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_garbage_a_row_does_not_own_never_reaches_it(interpreted, dtype):
    """NaN in every block no row has reached and in the tail of each row's
    last block past its position: the output is finite and the very same."""
    _, q, pool, tables = _case(3, 4, 6, 16, 2, 64, dtype)
    pos = np.asarray([5, 16, 47, 90], np.int32)
    clean = _kernel(q, pool, tables, jnp.asarray(pos), 2)
    owned = np.zeros(pool.shape[2:4], bool)               # (block, row)
    for r, p in enumerate(pos):
        for j in range(p + 1):
            owned[int(tables[r, j // 16]), j % 16] = True
    poisoned = jnp.where(jnp.asarray(owned)[None, None, :, :, None], pool,
                         jnp.nan)
    assert bool(jnp.isnan(poisoned[LAYER, :, 0]).all())   # the trash block
    got = _kernel(q, poisoned, tables, jnp.asarray(pos), 2)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, clean)


@pytest.mark.parametrize("m", [4, 5])
def test_dead_rows_attend_to_all_the_table_covers(interpreted, m):
    """The megastep feeds a retired row ``pos = m * block_size``, one past
    its table: it walks all m entries (a whole number of chunks or not) and
    attends to every position they hold, as the reference does; a position
    further out (the drafter's scan) reads the same."""
    _, q, pool, tables = _case(4, 3, m, 16, 2, 64, jnp.bfloat16)
    pos = jnp.asarray([m * 16, 7, m * 16 + 40], jnp.int32)
    got = _kernel(q, pool, tables, pos, 2)
    _agree(got, q, pool, tables, pos, 2)
    at_end = _kernel(q, pool, tables, jnp.full((3,), m * 16 - 1, jnp.int32),
                     2)
    np.testing.assert_array_equal(got[[0, 2]], at_end[[0, 2]])


@pytest.mark.parametrize("bucket", [1, 4, 32, 64])
def test_every_decode_bucket(interpreted, bucket):
    rs, q, pool, tables = _case(5, bucket, 3, 16, 1, 128, jnp.bfloat16,
                                n_blocks=200)
    pos = jnp.asarray(rs.randint(0, 48, (bucket,)), jnp.int32)
    _agree(_kernel(q, pool, tables, pos, 1), q, pool, tables, pos, 1)


@pytest.mark.parametrize("heads,head_dim", [(20, 64), (8, 128)])
def test_head_widths_of_the_served_models(interpreted, heads, head_dim):
    """GPT-2 large's 20 heads of 64 in a 1,280-wide row, and 8 of 128."""
    rs, q, pool, tables = _case(6, 3, 5, 16, heads, head_dim, jnp.bfloat16)
    pos = jnp.asarray([79, 33, 2], jnp.int32)
    got = _kernel(q, pool, tables, pos, heads)
    _agree(got, q, pool, tables, pos, heads)
    # heads do not mix: another head's query moves only its own lanes
    q2 = q.at[:, :head_dim].set(q[:, :head_dim] * 2)
    moved = np.abs(_kernel(q2, pool, tables, pos, heads) - got) > 0
    assert moved[:, :head_dim].any() and not moved[:, head_dim:].any()


def test_float32_pool_with_blocks_of_eight(interpreted):
    rs, q, pool, tables = _case(7, 4, 7, 8, 2, 64, jnp.float32)
    pos = jnp.asarray([0, 9, 30, 55], jnp.int32)
    _agree(_kernel(q, pool, tables, pos, 2), q, pool, tables, pos, 2)


def test_a_traced_layer_index_reads_that_layer(interpreted):
    """The speculative drafter and the megastep scan call the layers from
    inside a traced body: the layer's index may be a traced value."""
    _, q, pool, tables = _case(8, 2, 3, 16, 2, 64, jnp.bfloat16)
    pos = jnp.asarray([20, 47], jnp.int32)
    got = jax.jit(lambda layer: paged_decode_attention(
        q, pool, layer, tables, pos, 2))(jnp.int32(LAYER))
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  _kernel(q, pool, tables, pos, 2))


# -- the engine ---------------------------------------------------------------


def _serve(name, prompts, monkeypatch, kernel):
    monkeypatch.setattr(pa, "_INTERPRET", kernel)
    model = TransformerKVModel(61, 64, num_layers=2, num_heads=2,
                               num_embed=128)
    eng = ServingEngine(model, model.init_params(np.random.RandomState(5)),
                        max_batch=4, block_size=8, n_blocks=40,
                        prefill_buckets=[8, 16], decode_buckets=[2, 4],
                        max_new_tokens=6, sampling=False, name=name)
    eng.warmup()
    t0 = time.perf_counter()
    reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.run_until_idle(timeout=300)
    assert all(r.error is None for r in reqs) and eng.leaked_blocks() == 0
    its = [s["attrs"] for s in tracing.window(name, t0, time.perf_counter())
           if s["phase"] == "iteration"]
    assert its
    return [r.result() for r in reqs], {a["attn_kernel"] for a in its}


def test_engine_serves_the_same_greedy_tokens_with_the_kernel(monkeypatch):
    """A short served batch, decode launches of 2 and 4 rows with padding:
    the tokens are identical with the kernel (interpreted) and without, and
    the `iteration` record says which the launch's program was built with."""
    prompts = [list(range(1, 12)), list(range(20, 26)), list(range(30, 47))]
    plain, flag = _serve("pk0", prompts, monkeypatch, kernel=False)
    assert flag == {0}
    served, flag = _serve("pk1", prompts, monkeypatch, kernel=True)
    assert flag == {1}
    assert served == plain and all(len(t) == 6 for t in served)
