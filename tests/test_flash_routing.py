"""`_pick_impl` static routing, unit-tested on the CPU mesh.

Round-4 verdict weak #6: the flash-attention kernel *bodies* run in CI via
the interpreter (tests/test_pallas_interpret.py), but the routing that
decides which body runs (size gate at 512x512 score tiles, VMEM cap,
head_dim floor, env pins) was only exercised on-chip by the preflight — a
routing regression would ship green and only fail at bench time.  These
tests pin the decision table down where CI can see it.

The TPU-backend decisions are tested by monkeypatching
`jax.default_backend` — routing is pure trace-time logic over shapes and
env, so no kernel ever launches here.
"""
import importlib
import warnings

import jax
import jax.numpy as jnp
import pytest

fa = importlib.import_module(
    "mxnet_tpu.ops.pallas_kernels.flash_attention")


def q_of(s, d, dtype=jnp.bfloat16):
    return jnp.zeros((1, 2, s, d), dtype)


@pytest.fixture
def tpu_backend(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def test_cpu_backend_routes_to_jnp(monkeypatch):
    monkeypatch.delenv("MXNET_FLASH_IMPL", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert fa._pick_impl(q_of(1024, 64), 1024) == "jnp"


def test_default_is_hsd_on_tpu(tpu_backend, monkeypatch):
    monkeypatch.delenv("MXNET_FLASH_IMPL", raising=False)
    monkeypatch.delenv("MXNET_FLASH_LAYOUT", raising=False)
    assert fa._pick_impl(q_of(1024, 64), 1024) == "pallas_hsd"


def test_layout_env_opts_into_ds(tpu_backend, monkeypatch):
    monkeypatch.delenv("MXNET_FLASH_IMPL", raising=False)
    monkeypatch.setenv("MXNET_FLASH_LAYOUT", "ds")
    assert fa._pick_impl(q_of(1024, 64), 1024) == "pallas_ds"


@pytest.mark.parametrize("sq,skv,expect", [
    (512, 511, "jnp"),          # just under the 512x512 score-tile gate
    (512, 512, "pallas_hsd"),   # at the boundary the kernel wins
    (256, 512, "jnp"),          # 256*512 < 512*512
    (1024, 1024, "pallas_hsd"),
])
def test_size_gate_boundary(tpu_backend, monkeypatch, sq, skv, expect):
    monkeypatch.delenv("MXNET_FLASH_IMPL", raising=False)
    monkeypatch.delenv("MXNET_FLASH_LAYOUT", raising=False)
    assert fa._pick_impl(q_of(sq, 64), skv) == expect


def test_tiny_head_dim_routes_to_jnp(tpu_backend, monkeypatch):
    monkeypatch.delenv("MXNET_FLASH_IMPL", raising=False)
    assert fa._pick_impl(q_of(1024, 16), 1024) == "jnp"


def test_vmem_cap_routes_to_jnp(tpu_backend, monkeypatch):
    monkeypatch.delenv("MXNET_FLASH_IMPL", raising=False)
    # bf16 d=128: 1.25 * 8 * S * 128 * 2 bytes of margined double-buffered
    # whole-stream residency (round-5 on-chip anchors: S=4096 compiles at
    # block 512, S=8192 Mosaic-OOMs at any block at ~22% ABOVE linear
    # extrapolation) — the margined ~12 MB cap admits the verified S=4096
    # and falls back for the never-measured S=5120+ band instead of
    # risking a hard Mosaic compile error
    assert fa._pick_impl(q_of(4096, 128), 4096) == "pallas_hsd"
    assert fa._pick_impl(q_of(6144, 128), 6144) == "jnp"
    assert fa._pick_impl(q_of(8192, 128), 8192) == "jnp"


def test_pin_jnp_always_wins(tpu_backend, monkeypatch):
    monkeypatch.setenv("MXNET_FLASH_IMPL", "jnp")
    assert fa._pick_impl(q_of(4096, 128), 4096) == "jnp"


def test_pin_pallas_respected_on_ok_shape(tpu_backend, monkeypatch):
    monkeypatch.setenv("MXNET_FLASH_IMPL", "pallas_ds")
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # no spurious warning on a good pin
        assert fa._pick_impl(q_of(1024, 64), 1024) == "pallas_ds"


def test_pallas_is_imported_unconditionally():
    """No availability flag: on the installed packages the Pallas imports
    work or the program is broken, and an import error is an error."""
    assert not hasattr(fa, "_HAS_PALLAS")
    assert fa.pl.pallas_call and fa.pltpu.CompilerParams


def test_pin_on_rejected_shape_warns_but_honors_pin(tpu_backend,
                                                    monkeypatch):
    monkeypatch.setenv("MXNET_FLASH_IMPL", "pallas_hsd")
    with pytest.warns(UserWarning, match="auto-router would reject"):
        # over the VMEM cap: the pin stands but the user is told
        assert fa._pick_impl(q_of(16384, 128), 16384) == "pallas_hsd"


def test_block_size_env_override(monkeypatch):
    """MXNET_FLASH_BLOCK_Q/K pin the in-model block sizes (the
    DotProductAttention op builds with its own defaults, so the on-chip
    block A/B rides this env knob)."""
    captured = {}

    def fake_flash(q, k, v, qo, ko, scale, causal, bq, bk, impl):
        captured["blocks"] = (bq, bk)
        return q, jnp.zeros(q.shape[:3], jnp.float32)

    monkeypatch.setattr(fa, "_flash", fake_flash)
    monkeypatch.setenv("MXNET_FLASH_BLOCK_Q", "512")
    monkeypatch.setenv("MXNET_FLASH_BLOCK_K", "64")
    fa.flash_attention(q_of(256, 64), q_of(256, 64), q_of(256, 64),
                       block_q=128, block_k=128)
    assert captured["blocks"] == (512, 64)


@pytest.mark.parametrize("pin,expect", [(None, False), ("jnp", False),
                                        ("pallas_bsd", True),
                                        ("pallas_hsd", True)])
def test_bsd_eligibility_off_chip_follows_the_pin(monkeypatch, pin, expect):
    """Off the chip the bsd kernels are eligible only under a Pallas pin
    (the AOT-compile-from-CPU case); nothing else makes them so."""
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    monkeypatch.setattr(fa, "_INTERPRET", False)
    if pin is None:
        monkeypatch.delenv("MXNET_FLASH_IMPL", raising=False)
    else:
        monkeypatch.setenv("MXNET_FLASH_IMPL", pin)
    q = jnp.zeros((1, 1024, 256), jnp.bfloat16)
    assert fa._bsd_eligible(q, 2) is expect
    assert fa._bsd_eligible(q, 4) is False   # head_dim 64: never


def test_bsd_pin_warns_on_rejected_shape(monkeypatch):
    """head_dim 64 is not lane-aligned: the pin is honored but warned."""
    monkeypatch.setenv("MXNET_FLASH_IMPL", "pallas_bsd")
    captured = {}

    def fake(q, k, v, qo, ko, scale, causal, bq, bk, h, impl):
        captured["impl"] = impl
        return q, jnp.zeros((q.shape[0], h, q.shape[1]), jnp.float32)

    monkeypatch.setattr(fa, "_flash_bsd", fake)
    q = jnp.zeros((1, 1024, 256), jnp.bfloat16)
    with pytest.warns(UserWarning, match="auto-router would reject"):
        fa.flash_attention_bsd(q, q, q, 4)  # head_dim 64
    assert captured["impl"] == "pallas_bsd"


# ---- round-5 additions: auto blocks + bsd structure auto-promotion ----


def bsd_q(s, e, dtype=jnp.bfloat16):
    return jnp.zeros((1, s, e), dtype)


def test_auto_blocks_per_impl():
    # measured winners (round-5 on-chip block sweep, docs/mfu_roofline.md)
    assert fa._auto_blocks(0, 0, "pallas_hsd") == (512, 512)
    assert fa._auto_blocks(0, 0, "pallas_bsd") == (512, 512)
    assert fa._auto_blocks(0, 0, "pallas_bsd_gs") == (1024, 1024)
    assert fa._auto_blocks(0, 0, "pallas_ds") == (256, 256)
    assert fa._auto_blocks(0, 0, "jnp") == (256, 256)
    # explicit values always win over auto
    assert fa._auto_blocks(128, 256, "pallas_hsd") == (128, 256)
    # partial auto resolves only the unset side
    assert fa._auto_blocks(0, 256, "pallas_bsd_gs") == (1024, 256)


def test_bsd_structure_auto_promotes_past_vmem_cap(tpu_backend,
                                                   monkeypatch):
    monkeypatch.delenv("MXNET_FLASH_BSD_KERNEL", raising=False)
    # d=128 bf16: margined loop residency 1.25*8*S*128*2 crosses 12MB
    # above S=4915, so S=4096 stays loop and S=8192 streams
    assert fa._bsd_structure(bsd_q(4096, 768), 6, 4096) == "loop"
    assert fa._bsd_structure(bsd_q(8192, 768), 6, 8192) == "stream"


def test_bsd_structure_env_pin_wins(tpu_backend, monkeypatch):
    monkeypatch.setenv("MXNET_FLASH_BSD_KERNEL", "stream")
    assert fa._bsd_structure(bsd_q(1024, 768), 6, 1024) == "stream"
    monkeypatch.setenv("MXNET_FLASH_BSD_KERNEL", "loop")
    assert fa._bsd_structure(bsd_q(8192, 768), 6, 8192) == "loop"


def test_bsd_eligibility_lane_alignment(tpu_backend, monkeypatch):
    monkeypatch.delenv("MXNET_FLASH_IMPL", raising=False)
    assert fa._bsd_eligible(bsd_q(1024, 768), 6)        # d=128
    assert not fa._bsd_eligible(bsd_q(1024, 768), 12)   # d=64


def test_bsd_loop_pin_over_vmem_warns(tpu_backend, monkeypatch):
    """A pinned loop structure on an over-VMEM shape is honored but
    warned (auto would have promoted to the streamed structure)."""
    monkeypatch.setenv("MXNET_FLASH_BSD_KERNEL", "loop")
    monkeypatch.delenv("MXNET_FLASH_IMPL", raising=False)
    captured = {}

    def fake(q, k, v, qo, ko, scale, causal, bq, bk, h, impl):
        captured["impl"] = impl
        return q, jnp.zeros((q.shape[0], h, q.shape[1]), jnp.float32)

    monkeypatch.setattr(fa, "_flash_bsd", fake)
    q = bsd_q(8192, 768)
    with pytest.warns(UserWarning, match="MXNET_FLASH_BSD_KERNEL=loop"):
        fa.flash_attention_bsd(q, q, q, 6)
    assert captured["impl"] == "pallas_bsd"


def test_bsd_auto_promotes_impl_to_gs(tpu_backend, monkeypatch):
    monkeypatch.delenv("MXNET_FLASH_BSD_KERNEL", raising=False)
    monkeypatch.delenv("MXNET_FLASH_IMPL", raising=False)
    captured = {}

    def fake(q, k, v, qo, ko, scale, causal, bq, bk, h, impl):
        captured["impl"] = impl
        captured["blocks"] = (bq, bk)
        return q, jnp.zeros((q.shape[0], h, q.shape[1]), jnp.float32)

    monkeypatch.setattr(fa, "_flash_bsd", fake)
    q = bsd_q(8192, 768)
    fa.flash_attention_bsd(q, q, q, 6)
    assert captured["impl"] == "pallas_bsd_gs"
    assert captured["blocks"] == (1024, 1024)
