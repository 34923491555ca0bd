"""The routed experts' kernel against `held_share`'s loop.

The kernel body (`ops/pallas_kernels/expert_ffn.py`: `expert_ffn`) runs
through the Pallas interpreter on the CPU; the reference is what every
program the kernel does not take still runs: `ops.moe.held_share`'s
`fori_loop`, a step a tile.  One test per contract, one case per shape, so
that each counts.  (That the kernel lowers for the chip at the cells' widths
is `tests/test_aot_compile.py`'s.)
"""
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import tracing
from mxnet_tpu.ops import moe
from mxnet_tpu.ops.pallas_kernels import expert_ffn as ef
from mxnet_tpu.parallel.mesh import MeshContext, make_mesh
from mxnet_tpu.serving import ServingEngine, ShortConvMoEKVModel


def _banks(rs, n_held, d, f, dtype):
    return tuple(jnp.asarray(rs.randn(*shape) * 0.1, dtype)
                 for shape in ((n_held, d, f), (n_held, d, f),
                               (n_held, f, d)))


def _routes(rs, n, n_routed, top_k, only=None):
    """(idx, w): ``top_k`` distinct experts a row of ``n_routed`` (of
    ``only`` where given) and their weights."""
    pool = np.arange(n_routed) if only is None else np.asarray(only)
    idx = np.stack([rs.choice(pool, top_k, replace=False) for _ in range(n)])
    return (jnp.asarray(idx, jnp.int32),
            jnp.asarray(rs.rand(n, top_k), jnp.float32))


def _share(u, idx, w, banks, held, valid, kernel, monkeypatch):
    """`held_share` through the kernel (interpreted) or through the loop."""
    monkeypatch.setattr(ef, "_INTERPRET", kernel)
    assert bool(moe.expert_kernel_applies(u.shape[0], banks)) == kernel
    y, counts = jax.jit(lambda *a: moe.held_share(
        *a, experts_held=held, valid=valid))(u, idx, w, *banks)
    assert y.shape == u.shape and y.dtype == jnp.float32
    return np.asarray(y), np.asarray(counts)


# (rows, width, FFN width, routed experts, top-k, held range, dtype, what):
# experts no row chose; an expert given more rows than a tile; padding rows
# (NaN in them); a held sub-range of the routed experts, as a chip of a
# wide deployment holds (Kimi's 12 of 384, MiMo's 16 of 256, at small
# sizes); one row and 64 rows; the FFN width in several blocks
CASES = {
    "experts-no-row-chose": (24, 128, 256, 8, 2, (0, 8), "f32", "unchosen"),
    "an-expert-over-a-tile": (200, 128, 128, 6, 2, (0, 6), "bf16", "full"),
    "padding-rows": (48, 256, 128, 8, 2, (0, 8), "bf16", "padding"),
    "held-sub-range": (64, 128, 256, 48, 4, (12, 16), "bf16", ""),
    "one-row": (1, 128, 128, 16, 4, (4, 12), "f32", ""),
    "64-rows": (64, 256, 256, 32, 4, (0, 32), "bf16", ""),
    "ffn-width-in-blocks": (40, 128, 384, 8, 2, (0, 8), "f32", "blocks"),
}


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_equals_the_loop(monkeypatch, case):
    n, d, f, routed, top_k, held, dt, what = CASES[case]
    dtype = jnp.bfloat16 if dt == "bf16" else jnp.float32
    rs = np.random.RandomState(len(case))
    banks = _banks(rs, held[1] - held[0], d, f, dtype)
    idx, w = _routes(rs, n, routed, top_k,
                     only=[0, 3, 5] if what == "unchosen" else None)
    u = jnp.asarray(rs.randn(n, d), dtype)
    valid = None
    if what == "full":
        # every row's first choice is expert 2: 200 rows, two tiles
        idx = idx.at[:, 0].set(2).at[:, 1].set(jnp.where(idx[:, 1] == 2, 3,
                                                         idx[:, 1]))
    if what == "padding":
        valid = jnp.asarray(rs.rand(n) > 0.3)
        u = jnp.where(valid[:, None], u, jnp.nan).astype(dtype)
    if what == "blocks":
        # slots for one 128-wide block of the width: three steps a tile
        monkeypatch.setattr(ef, "_WEIGHT_BYTES", 6 * d * 128 * 4)
        assert ef.ffn_block(d, f, 4) == 128
    want, want_counts = _share(u, idx, w, banks, held, valid, False,
                               monkeypatch)
    got, counts = _share(u, idx, w, banks, held, valid, True, monkeypatch)
    np.testing.assert_array_equal(counts, want_counts)
    if what == "unchosen":
        assert (counts[[1, 2, 4, 6, 7]] == 0).all() and counts.sum() > 0
    if what == "full":
        assert counts[2] == n > ef.tile_rows(n)
    if what == "padding":
        # a padding row is routed nowhere and its NaN reaches no row
        assert np.isfinite(got).all()
        assert not got[~np.asarray(valid)].any()
    # the same products; a width in blocks sums ``h W_d`` in parts
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_a_rows_result_does_not_depend_on_the_rest_of_its_batch(
        monkeypatch):
    """Row 5 of a launch of 64 rows, alone (the others padding) and among
    63 others that share its experts' tiles: the same bits."""
    monkeypatch.setattr(ef, "_INTERPRET", True)
    rs = np.random.RandomState(3)
    n, d, f = 64, 128, 256
    banks = _banks(rs, 8, d, f, jnp.bfloat16)
    idx, w = _routes(rs, n, 8, 2)
    u = jnp.asarray(rs.randn(n, d), jnp.bfloat16)
    share = jax.jit(lambda u, idx, w, valid: moe.held_share(
        u, idx, w, *banks, experts_held=(0, 8), valid=valid)[0])
    together = np.asarray(share(u, idx, w, jnp.ones((n,), bool)))
    alone = np.asarray(share(u, idx, w, jnp.arange(n) == 5))
    np.testing.assert_array_equal(alone[5], together[5])
    assert not np.delete(alone, 5, axis=0).any()


def test_gate_refuses_what_the_kernel_does_not_take(monkeypatch):
    def banks(d=128, f=256, dtype=jnp.bfloat16):
        return tuple(jax.ShapeDtypeStruct(s, dtype)
                     for s in ((4, d, f), (4, d, f), (4, f, d)))

    assert not moe.expert_kernel_applies(64, banks())             # no TPU
    monkeypatch.setattr(ef, "_INTERPRET", True)
    assert moe.expert_kernel_applies(64, banks())
    assert moe.expert_kernel_applies(1, banks(dtype=jnp.float32))
    # widths that are not whole 128-lane tiles
    assert not moe.expert_kernel_applies(64, banks(d=64))
    assert not moe.expert_kernel_applies(64, banks(f=96))
    assert not moe.expert_kernel_applies(64, banks(dtype=jnp.int8))
    # rows and an accumulator that would not fit the kernel's VMEM
    assert not moe.expert_kernel_applies(1 << 14, banks(d=2048))
    # the served widths fit: LFM2's, Kimi's share, MiMo's share
    for d, f in ((2048, 1792), (7168, 2048), (4096, 2048)):
        assert moe.expert_kernel_applies(512, banks(d, f))
    # a program built for a mesh of more than one device
    with MeshContext(make_mesh(devices=jax.devices()[:2])):
        assert not moe.expert_kernel_applies(64, banks())
    with MeshContext(make_mesh(devices=jax.devices()[:1])):
        assert moe.expert_kernel_applies(64, banks())


def test_tiles_bound_what_the_pairs_can_fill():
    # LFM2's chunk: 512 rows, top-4 of 32 held, tiles of 128
    assert ef.max_tiles(512, 4, 32, 128) == 47
    # a decode launch of 64 rows over Kimi's 12 held of 384
    assert ef.max_tiles(64, 8, 12, 64) == 12
    # one row: one tile an expert it can pick
    assert ef.max_tiles(1, 4, 8, 16) == 4
    assert ef.tile_rows(1) == 16 and ef.tile_rows(512) == 128


# -- the engine ---------------------------------------------------------------


def _serve(name, monkeypatch, kernel):
    monkeypatch.setattr(ef, "_INTERPRET", kernel)
    model = ShortConvMoEKVModel(61, 64, ["conv", "full_attention", "conv"],
                                128, 2, 2, 3, 64, 128, 4, 2,
                                num_dense_layers=1)
    eng = ServingEngine(model, model.init_params(np.random.RandomState(5),
                                                 scale=0.2),
                        max_batch=2, block_size=8, n_blocks=20,
                        prefill_buckets=[16], decode_buckets=[2],
                        sampling=False, name=name)
    t0 = time.perf_counter()
    reqs = [eng.submit(p, max_new_tokens=3)
            for p in (list(range(1, 12)), list(range(30, 36)))]
    eng.run_until_idle(timeout=300)
    assert all(r.error is None for r in reqs)
    its = [s["attrs"] for s in tracing.window(name, t0, time.perf_counter())
           if s["phase"] == "iteration"]
    assert its
    return [r.result() for r in reqs], {a["expert_kernel"] for a in its}


def test_iteration_records_say_whether_the_experts_ran_as_the_kernel(
        monkeypatch):
    """The `iteration` record's `expert_kernel` is what
    `expert_kernel_applies` says for the engine's banks: 0 on the CPU, 1
    where the kernel runs (here through the interpreter); the tokens are the
    same."""
    plain, flag = _serve("efk0", monkeypatch, kernel=False)
    assert flag == {0}
    served, flag = _serve("efk1", monkeypatch, kernel=True)
    assert flag == {1}
    assert served == plain
