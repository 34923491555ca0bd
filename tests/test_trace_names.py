"""The names the program puts on its work (ISSUE-25, part B): every scope,
program name and kernel name is found in what the programs lower to, so a
refactor that drops one fails here, not in the next chip trace.

`lower(...).as_text(debug_info=True)` carries the name stack of every
operation as its location; a compiled program's text carries it as
`op_name`, and its first line names the module as the profiler's "XLA
Modules" line will."""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import models
from mxnet_tpu.parallel import SPMDTrainer, make_mesh
from mxnet_tpu.serving import (HybridDeltaKVModel, HybridWindowMoEKVModel,
                               LatentMoEKVModel, ServingEngine,
                               ShortConvMoEKVModel, TransformerKVModel)

SERVING_SCOPES = {"embed", "qkv_proj", "kv_scatter", "kv_gather", "attn_out",
                  "ffn", "lm_head", "sampler"}


def _scopes(text):
    """Every scope any operation of a compiled program's text lies under."""
    out = set()
    for name in re.findall(r'op_name="([^"]+)"', text):
        out.update(re.sub(r"^(?:\w+\()+|\)+$", "", part)
                   for part in name.split("/")[1:-1])
    return out


@pytest.fixture(scope="module")
def engine():
    model = TransformerKVModel(61, 32, num_layers=2, num_heads=2,
                               num_embed=32)
    return ServingEngine(model, model.init_params(np.random.RandomState(3)),
                         max_batch=4, block_size=4, n_blocks=32,
                         prefill_buckets=[8], decode_buckets=[2],
                         megastep_steps=2, spec_k=2, name="names")


@pytest.mark.parametrize("build,module,attention", [
    ("_compiled_decode", "jit_serve_decode_b2", "decode_attention"),
    ("_compiled_prefill", "jit_serve_prefill_s8", "chunk_attention"),
    ("_compiled_mega", "jit_serve_mega_b2", "decode_attention"),
    ("_compiled_verify", "jit_serve_verify_b2", "verify_attention"),
])
def test_serving_programs_carry_their_name_and_scopes(engine, build, module,
                                                      attention):
    bucket = 8 if build == "_compiled_prefill" else 2
    if build == "_compiled_mega":
        engine._mega_m = 2
    if build == "_compiled_verify":
        engine._spec_k = 2
    text = getattr(engine, build)(bucket).as_text()
    assert text.startswith("HloModule %s," % module)
    assert _scopes(text) >= SERVING_SCOPES | {attention}


LATENT_SCOPES = {"embed", "mla_q_proj", "mla_kv_proj", "latent_scatter",
                 "attn_out", "ffn", "moe_router", "moe_dispatch",
                 "moe_experts", "moe_shared", "moe_combine", "moe_loop",
                 "lm_head", "sampler"}


@pytest.fixture(scope="module")
def latent_engine():
    model = LatentMoEKVModel(61, 32, 2, 32, 2, 12, 8, 8, 4, 8, 48, 16, 8,
                             (2, 6), 2, routed_scaling_factor=2.0)
    return ServingEngine(model, model.init_params(np.random.RandomState(3)),
                         max_batch=4, block_size=4, n_blocks=32,
                         prefill_buckets=[8], decode_buckets=[2],
                         name="latent_names")


@pytest.mark.parametrize("build,module,attention", [
    ("_compiled_decode", "jit_serve_decode_b2", {"decode_attention"}),
    ("_compiled_prefill", "jit_serve_prefill_s8",
     {"mla_prefill_attention", "mla_prefill_loop"}),
])
def test_latent_programs_carry_their_name_and_scopes(latent_engine, build,
                                                     module, attention):
    """The second model class under the engine's own program names: what
    the `.kimi` metrics' readers look for (`benchmark/metrics/*.kimi.json`)."""
    bucket = 8 if build == "_compiled_prefill" else 2
    text = getattr(latent_engine, build)(bucket).as_text()
    assert text.startswith("HloModule %s," % module)
    assert _scopes(text) >= LATENT_SCOPES | attention


SHORTCONV_SCOPES = {"embed", "short_conv", "conv_state", "qkv_proj",
                    "qk_norm", "rope", "kv_scatter", "attn_out", "ffn",
                    "moe_router", "moe_dispatch", "moe_experts",
                    "moe_combine", "moe_loop", "lm_head", "sampler"}


@pytest.fixture(scope="module")
def shortconv_engine():
    model = ShortConvMoEKVModel(
        61, 32, ["conv", "full_attention", "conv"], 32, 4, 2, 3, 48, 16, 8,
        2, num_dense_layers=1)
    return ServingEngine(model, model.init_params(np.random.RandomState(3)),
                         max_batch=4, block_size=4, n_blocks=32,
                         prefill_buckets=[8], decode_buckets=[2],
                         name="shortconv_names")


@pytest.mark.parametrize("build,module,attention", [
    ("_compiled_decode", "jit_serve_decode_b2",
     {"decode_attention", "kv_gather"}),
    ("_compiled_prefill", "jit_serve_prefill_s8",
     {"chunk_attention", "kv_gather"}),
])
def test_shortconv_programs_carry_their_name_and_scopes(shortconv_engine,
                                                        build, module,
                                                        attention):
    """The third model class under the engine's own program names: what the
    `.lfm2` metrics' readers look for (`benchmark/metrics/*.lfm2.json`).  No
    shared expert, so no `moe_shared`."""
    bucket = 8 if build == "_compiled_prefill" else 2
    text = getattr(shortconv_engine, build)(bucket).as_text()
    assert text.startswith("HloModule %s," % module)
    scopes = _scopes(text)
    assert scopes >= SHORTCONV_SCOPES | attention
    assert "moe_shared" not in scopes


def test_the_state_seams_span_attribute_and_counters_are_named(
        shortconv_engine):
    """`state_slots_live` on the `iteration` record and as a gauge, and the
    `state_resets` counter: what `state_slots_live_peak_share.lfm2` reads."""
    import time

    from mxnet_tpu import telemetry, tracing

    reg = telemetry.registry()
    before = reg.counter("serve.shortconv_names.state_resets").value
    t0 = time.perf_counter()
    req = shortconv_engine.submit([5, 6, 7, 8, 9], max_new_tokens=3)
    shortconv_engine.run_until_idle(timeout=120)
    assert len(req.result(timeout=5)) == 3
    records = [r["attrs"] for r in tracing.window(
        "shortconv_names", t0, time.perf_counter())
        if r["phase"] == "iteration"]
    assert records and all(a["state_slots_live"] == 1 for a in records)
    assert all({"expert_rows", "expert_hits", "expert_load_max"} <= set(a)
               for a in records)
    assert reg.counter("serve.shortconv_names.state_resets").value \
        == before + 1
    assert reg.gauge("serve.shortconv_names.state_slots_live").value == 1


WINDOW_SCOPES = {"embed", "qkv_proj", "rope", "window_state",
                 "window_attention", "kv_scatter", "attn_out", "ffn",
                 "moe_router", "moe_dispatch", "moe_experts", "moe_combine",
                 "moe_loop", "lm_head", "sampler"}


@pytest.fixture(scope="module")
def window_engine():
    model = HybridWindowMoEKVModel(
        61, 32, [0, 1, 1], [0, 1, 1], 32, 4, 1, 2, 16, 8, 8, 4, 48, 16, 8,
        (0, 4), 2)
    return ServingEngine(model, model.init_params(np.random.RandomState(3)),
                         max_batch=4, block_size=4, n_blocks=32,
                         prefill_buckets=[8], decode_buckets=[2],
                         name="window_names")


@pytest.mark.parametrize("build,module,attention", [
    ("_compiled_decode", "jit_serve_decode_b2",
     {"decode_attention", "kv_gather"}),
    ("_compiled_prefill", "jit_serve_prefill_s8", {"chunk_attention"}),
])
def test_window_programs_carry_their_name_and_scopes(window_engine, build,
                                                     module, attention):
    """The fourth model class under the engine's own program names: what the
    `.mimo` metrics' readers look for (`benchmark/metrics/*.mimo.json`):
    the window layers' `window_attention` and `window_state`, the full
    layers' `decode_attention` and `chunk_attention` (a chunk walks the
    table in steps, so it gathers under `chunk_attention`, not
    `kv_gather`), the partial `rope`.  No shared expert."""
    bucket = 8 if build == "_compiled_prefill" else 2
    text = getattr(window_engine, build)(bucket).as_text()
    assert text.startswith("HloModule %s," % module)
    scopes = _scopes(text)
    assert scopes >= WINDOW_SCOPES | attention
    assert "moe_shared" not in scopes


def test_the_rings_attribute_and_counters_are_named(window_engine):
    """`state_slots_live` and `full_ctx_blocks` on the `iteration` record,
    and the `ring_resets` counter: what `state_slots_live_peak_share.mimo`
    and `gqa_decode_roofline.mimo` read."""
    import time

    from mxnet_tpu import telemetry, tracing

    reg = telemetry.registry()
    before = reg.counter("serve.window_names.ring_resets").value
    t0 = time.perf_counter()
    req = window_engine.submit([5, 6, 7, 8, 9], max_new_tokens=3)
    window_engine.run_until_idle(timeout=120)
    assert len(req.result(timeout=5)) == 3
    records = [r["attrs"] for r in tracing.window(
        "window_names", t0, time.perf_counter())
        if r["phase"] == "iteration"]
    assert records and all(a["state_slots_live"] == 1 for a in records)
    decodes = [a for a in records if "ctx_blocks" in a]
    assert decodes and all(a["full_ctx_blocks"] == a["ctx_blocks"]
                           for a in decodes)     # one full layer
    assert reg.counter("serve.window_names.ring_resets").value \
        == before + 1
    assert reg.counter("serve.window_names.state_resets").value == 0


DELTA_SCOPES = {"embed", "qkv_proj", "short_conv", "delta_rule",
                "linear_state", "qk_norm", "kv_scatter", "attn_out", "ffn",
                "lm_head", "sampler"}


@pytest.fixture(scope="module")
def delta_engine():
    model = HybridDeltaKVModel(
        61, 32, ["linear_attention", "full_attention", "linear_attention"],
        32, 2, 2, 2, 8, 16, 4, 48, chunk_size=4)
    return ServingEngine(model, model.init_params(np.random.RandomState(3)),
                         max_batch=4, block_size=4, n_blocks=32,
                         prefill_buckets=[8], decode_buckets=[2],
                         name="delta_names")


@pytest.mark.parametrize("build,module,attention", [
    ("_compiled_decode", "jit_serve_decode_b2",
     {"decode_attention", "kv_gather"}),
    ("_compiled_prefill", "jit_serve_prefill_s8", {"chunk_attention"}),
])
def test_delta_programs_carry_their_name_and_scopes(delta_engine, build,
                                                    module, attention):
    """The fifth model class under the engine's own program names: what the
    `.olmo` metrics' readers look for (`benchmark/metrics/*.olmo.json`):
    the linear layers' `delta_rule` (the chunked form in prefill, the step
    in decode), `linear_state` (the slots' read and write) and
    `short_conv`, the full layers' `decode_attention` and
    `chunk_attention` (a chunk walks the table in steps)."""
    bucket = 8 if build == "_compiled_prefill" else 2
    text = getattr(delta_engine, build)(bucket).as_text()
    assert text.startswith("HloModule %s," % module)
    scopes = _scopes(text)
    assert scopes >= DELTA_SCOPES | attention
    assert not {"rope", "moe_router"} & scopes


def test_the_delta_state_slot_bytes_and_gauges_are_named(delta_engine):
    """`state_slot_bytes` in the engine's stats (from the model),
    `state_slots_live` on the `iteration` record and the `state_resets`
    counter: what `state_slots_live_peak_share.olmo` reads and PERF.md's
    memory accounting quotes."""
    import time

    from mxnet_tpu import telemetry, tracing

    model = delta_engine.model
    assert delta_engine.stats["state_slot_bytes"] \
        == model.state_slot_bytes() > 0
    reg = telemetry.registry()
    before = reg.counter("serve.delta_names.state_resets").value
    t0 = time.perf_counter()
    req = delta_engine.submit([5, 6, 7, 8, 9], max_new_tokens=3)
    delta_engine.run_until_idle(timeout=120)
    assert len(req.result(timeout=5)) == 3
    records = [r["attrs"] for r in tracing.window(
        "delta_names", t0, time.perf_counter())
        if r["phase"] == "iteration"]
    assert records and all(a["state_slots_live"] == 1 for a in records)
    assert any("ctx_blocks" in a for a in records)
    assert reg.counter("serve.delta_names.state_resets").value == before + 1


def test_latent_prefill_lowered_for_a_tpu_is_its_kernel(monkeypatch):
    """Lowered for a TPU as a one-device program, `serve_prefill_s*` of the
    latent model calls one lowered function a layer under
    `mla_prefill_attention`, whose body is the kernel `latent_prefill_attn`,
    and holds no loop: what `mla_prefill_attn_ms_per_chunk.kimi` and
    `mla_prefill_roofline.kimi` read on the chip.  (The CPU programs above
    keep the `lax` loop and both of its scopes.)"""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    layers, bs = 2, 8
    model = LatentMoEKVModel(61, 64, layers, 32, 2, 12, 128, 8, 4, 8, 48, 16,
                             8, (2, 6), 2, routed_scaling_factor=2.0)

    def serve_prefill_s8(params, pool, tokens, start, length, tables):
        return model.prefill_paged(params, pool, tokens, start, length,
                                   tables)

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    params = {k: jax.ShapeDtypeStruct(v, jnp.float32)
              for k, v in model.param_shapes().items()}
    pool = jax.ShapeDtypeStruct((layers, 32, bs, model.pool_width),
                                jnp.float32)
    text = jax.jit(serve_prefill_s8).trace(
        params, pool, ints(1, bs), ints(1), ints(1), ints(1, 8)).lower(
            lowering_platforms=("tpu",)).as_text(debug_info=True)
    names = set(re.findall(r'loc\("([^"]+)"', text))
    assert "jit(serve_prefill_s8)/mla_prefill_attention/" \
        "jit(_latent_prefill)" in names
    assert "latent_prefill_attn/pallas_call" in names
    assert len(re.findall(r"call @_latent_prefill\b", text)) == layers
    assert text.count("tpu_custom_call") == 1          # one lowered function
    assert not any("mla_prefill_loop" in n for n in names)


def test_pool_programs_carry_their_names(engine):
    assert engine._compiled_cow().as_text().startswith(
        "HloModule jit_serve_cow,")
    assert engine._compiled_restore(2).as_text().startswith(
        "HloModule jit_serve_restore_k2,")


def test_train_step_runs_each_node_and_the_optimizer_under_its_name():
    net = models.get_transformer_lm(vocab_size=64, seq_len=16, num_layers=1,
                                    num_heads=2, num_embed=32,
                                    num_ffn_hidden=64, fused_head=True)
    mesh = make_mesh(shape=(1,), axis_names=("data",),
                     devices=jax.devices()[:1])
    mx.random.seed(0)
    trainer = SPMDTrainer(net, mesh, data_shapes={"data": (2, 16),
                                                  "softmax_label": (2, 16)},
                          optimizer="adam", abstract=True)
    text = trainer.lower_step(
        {"data": np.int32, "softmax_label": np.int32}).as_text()
    assert text.startswith("HloModule jit_step,")
    scopes = _scopes(text)
    # forward and backward of a node both read as the node
    assert scopes >= {"optimizer", "pred", "embed", "layer0_attn",
                      "layer0_ffn1", "final_ln"}
    assert re.search(r'op_name="jit\(step\)/transpose\(jvp\(pred\)\)/', text)
    assert re.search(r'op_name="jit\(step\)/jvp\(layer0_ffn1\)/', text)


def test_train_steps_reach_the_profilers_trace(tmp_path):
    """`SPMDTrainer.step` is a `train_step` span on the host's plane of a
    profiler trace, one a step: what `dispatch_ms.train` reads (with the
    benchmark's raw reader, as it does)."""
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmark import xplane_raw
    from benchmark.readers import span_ms

    net = models.get_transformer_lm(vocab_size=64, seq_len=16, num_layers=1,
                                    num_heads=2, num_embed=32,
                                    num_ffn_hidden=64, fused_head=True)
    mesh = make_mesh(shape=(1,), axis_names=("data",),
                     devices=jax.devices()[:1])
    mx.random.seed(0)
    trainer = SPMDTrainer(net, mesh, data_shapes={"data": (2, 16),
                                                  "softmax_label": (2, 16)},
                          optimizer="adam")
    batch = {"data": np.ones((2, 16), np.int32),
             "softmax_label": np.ones((2, 16), np.int32)}
    trainer.step(batch)                      # compiled outside the trace
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for _ in range(3):
            outs = trainer.step(batch)
        jax.block_until_ready(outs)
    finally:
        jax.profiler.stop_trace()
    path = xplane_raw.trace.find_xplane(str(tmp_path))
    spans = [(s, d, m["name"].split("#")[0], line)
             for plane in xplane_raw.planes(
                 path, host_prefixes=xplane_raw.PROGRAM_SPANS)
             if plane["name"].startswith("/host:")
             for line, events in plane["lines"].items()
             for s, d, m in events]
    assert [name for _, _, name, _ in spans] == ["train_step"] * 3
    assert all(d > 0 for _, d, _, _ in spans)
    # the reader, on what a device trace would hold beside them
    notes = []
    run = type("Run", (), {"_raw": {"ops": [], "modules": [],
                                    "spans": sorted(spans)},
                           "note": notes.append})()
    assert span_ms.read(run, span="train_step", q=50) \
        == pytest.approx(sorted(d for _, d, _, _ in spans)[1] / 1e6)


@pytest.fixture
def for_tpu(monkeypatch):
    """The kernels' backend gates see a TPU, and `lower` targets one: the
    Mosaic kernels are serialised at lowering, with no chip and no TPU
    compiler (nothing is compiled)."""
    for pin in ("MXNET_FLASH_IMPL", "MXNET_FLASH_BSD_KERNEL", "MXNET_LN_IMPL",
                "MXNET_FLASH_LAYOUT", "MXNET_FLASH_BWD", "MXNET_CE_SHARD",
                "MXNET_CE_SINGLE_PASS"):
        monkeypatch.delenv(pin, raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def lower(fn, *shapes):
        return jax.jit(fn).trace(*shapes).lower(
            lowering_platforms=("tpu",)).as_text(debug_info=True)

    return lower


def _kernels(text):
    return set(re.findall(r'kernel_name = "(\w+)"', text))


FLASH_KERNELS = {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}


@pytest.mark.parametrize("pin", [None, "pallas_ds"])
def test_flash_kernels_are_named_in_their_lowered_calls(for_tpu,
                                                        monkeypatch, pin):
    from mxnet_tpu.ops.pallas_kernels import flash_attention

    if pin:
        monkeypatch.setenv("MXNET_FLASH_IMPL", pin)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True)
                       .astype(jnp.float32))

    sh = jax.ShapeDtypeStruct((2, 4, 512, 64), jnp.bfloat16)
    text = for_tpu(jax.grad(loss, argnums=(0, 1, 2)), sh, sh, sh)
    assert _kernels(text) == FLASH_KERNELS
    # the name is a scope too: it reaches the trace's `tf_op`
    assert 'jvp(flash_fwd)/pallas_call"' in text
    assert 'transpose(jvp(flash_bwd_dq))/pallas_call"' in text


@pytest.mark.parametrize("structure", ["loop", "stream"])
def test_flash_bsd_kernels_share_the_names(for_tpu, monkeypatch, structure):
    from mxnet_tpu.ops.pallas_kernels.flash_attention import \
        flash_attention_bsd

    monkeypatch.setenv("MXNET_FLASH_BSD_KERNEL", structure)

    def loss(q, k, v):
        return jnp.sum(flash_attention_bsd(q, k, v, 2, causal=True)
                       .astype(jnp.float32))

    sh = jax.ShapeDtypeStruct((2, 512, 256), jnp.bfloat16)
    text = for_tpu(jax.grad(loss, argnums=(0, 1, 2)), sh, sh, sh)
    assert _kernels(text) == FLASH_KERNELS


@pytest.mark.parametrize("single_pass,names", [
    ("1", {"fused_ce_fwd", "fused_ce_bwd_dw"}),
    ("0", {"fused_ce_fwd", "fused_ce_bwd_dx", "fused_ce_bwd_dw"}),
])
def test_fused_ce_kernels_are_named(for_tpu, monkeypatch, single_pass,
                                    names):
    from mxnet_tpu.ops.pallas_kernels import fused_softmax_ce

    monkeypatch.setenv("MXNET_CE_SINGLE_PASS", single_pass)

    def loss(x, w, label):
        return jnp.sum(fused_softmax_ce(x, w, None, label))

    text = for_tpu(jax.grad(loss, argnums=(0, 1)),
                   jax.ShapeDtypeStruct((2048, 256), jnp.bfloat16),
                   jax.ShapeDtypeStruct((2048, 256), jnp.bfloat16),
                   jax.ShapeDtypeStruct((2048,), jnp.int32))
    assert _kernels(text) == names


def test_layer_norm_kernels_are_named(for_tpu):
    from mxnet_tpu.ops.pallas_kernels.layer_norm import layer_norm

    def loss(x, g, b):
        return jnp.sum(layer_norm(x, g, b, 1e-5).astype(jnp.float32))

    text = for_tpu(jax.grad(loss, argnums=(0, 1, 2)),
                   jax.ShapeDtypeStruct((1024, 256), jnp.bfloat16),
                   jax.ShapeDtypeStruct((256,), jnp.float32),
                   jax.ShapeDtypeStruct((256,), jnp.float32))
    assert _kernels(text) == {"layer_norm_fwd", "layer_norm_bwd"}


@pytest.mark.parametrize("pool_dtype,kernel", [
    (jnp.bfloat16, True),      # the served pool: the kernel, and no gather
    (jnp.int8, False),         # not the kernel's: the `jax.numpy` body
])
def test_paged_decode_attention_is_named_either_way(for_tpu, pool_dtype,
                                                    kernel):
    """`kv_gather_attn_ms_per_launch` reads the scopes `kv_gather` and
    `decode_attention`: on a TPU the paged kernel runs under the second
    with a name of its own, and a pool it does not take keeps both."""
    from mxnet_tpu.ops.attention import paged_decode_attention

    text = for_tpu(
        lambda q, pool, tables, pos: paged_decode_attention(
            q, pool, 1, tables, pos, 2),
        jax.ShapeDtypeStruct((4, 128), jnp.bfloat16),
        jax.ShapeDtypeStruct((2, 2, 16, 32, 128), pool_dtype),
        jax.ShapeDtypeStruct((4, 8), jnp.int32),
        jax.ShapeDtypeStruct((4,), jnp.int32))
    if kernel:
        assert _kernels(text) == {"paged_decode_attn"}
        # the kernel's own name inside the function the layers share, and
        # the scope on that function's call (a compiled program's `op_name`
        # joins the two: `tests/test_aot_compile.py`)
        assert '"paged_decode_attn/pallas_call"' in text
        assert 'decode_attention/jit(_paged_decode)"' in text
        assert "kv_gather" not in text
    else:
        assert _kernels(text) == set()
        assert 'kv_gather/gather"' in text
        assert 'decode_attention/' in text


@pytest.mark.parametrize("pool_dtype,heads,kernel", [
    (jnp.bfloat16, 2, True),    # GPT-2's layout: a K/V head a query head
    (jnp.bfloat16, 8, True),    # LFM2's: 8 query heads over 2 K/V heads
    (jnp.int8, 2, False),       # not the kernel's: the `jax.numpy` body
])
def test_paged_prefill_attention_is_named_either_way(for_tpu, pool_dtype,
                                                     heads, kernel):
    """`prefill_attn_ms_per_chunk`, `gqa_prefill_attn_ms_per_chunk.lfm2` and
    their rooflines read the scopes `chunk_attention` and `kv_gather`: on a
    TPU the prefill kernel runs under the first with a name of its own, and
    a pool it does not take keeps both."""
    from mxnet_tpu.ops.attention import paged_chunk_attention

    text = for_tpu(
        lambda q, pool, tables, start: paged_chunk_attention(
            q, pool, 1, tables, start, heads,
            kv_heads=None if heads == 2 else 2),
        jax.ShapeDtypeStruct((1, 64, heads * 64), jnp.bfloat16),
        jax.ShapeDtypeStruct((2, 2, 16, 32, 128), pool_dtype),
        jax.ShapeDtypeStruct((1, 8), jnp.int32),
        jax.ShapeDtypeStruct((1,), jnp.int32))
    if kernel:
        assert _kernels(text) == {"paged_prefill_attn"}
        assert '"paged_prefill_attn/pallas_call"' in text
        assert 'chunk_attention/jit(_paged_prefill)"' in text
        assert "kv_gather" not in text
    else:
        assert _kernels(text) == set()
        assert 'kv_gather/gather"' in text
        assert 'chunk_attention/' in text


def test_grouped_query_decode_is_the_paged_kernel_and_no_gather(for_tpu):
    """`gqa_decode_attn_ms_per_launch.lfm2` reads the scope
    `decode_attention`: over a pool of fewer K/V heads the same kernel runs
    under it, with the query's layout and the result's fold beside it in the
    function the layers share, and nothing gathers the table's width."""
    from mxnet_tpu.ops.attention import paged_decode_attention

    text = for_tpu(
        lambda q, pool, tables, pos: paged_decode_attention(
            q, pool, 1, tables, pos, 8, kv_heads=2),
        jax.ShapeDtypeStruct((4, 512), jnp.bfloat16),
        jax.ShapeDtypeStruct((2, 2, 16, 32, 128), jnp.bfloat16),
        jax.ShapeDtypeStruct((4, 8), jnp.int32),
        jax.ShapeDtypeStruct((4,), jnp.int32))
    assert _kernels(text) == {"paged_decode_attn"}
    assert '"paged_decode_attn/pallas_call"' in text
    assert 'decode_attention/jit(_paged_decode)"' in text
    assert "kv_gather" not in text


def test_latent_decode_kernel_is_named_in_its_lowered_call(for_tpu):
    """`mla_decode_attn_ms_per_launch.kimi` and `mla_decode_roofline.kimi`
    read the scope `decode_attention`: on a TPU the latent kernel runs under
    it with a name of its own, inside the function the layers share."""
    from mxnet_tpu.ops.latent_attention import latent_decode_attention

    text = for_tpu(
        lambda q, pool, tables, pos: latent_decode_attention(
            q, pool, 1, tables, pos, 128, 0.1),
        jax.ShapeDtypeStruct((4, 16, 256), jnp.bfloat16),
        jax.ShapeDtypeStruct((2, 16, 32, 256), jnp.bfloat16),
        jax.ShapeDtypeStruct((4, 8), jnp.int32),
        jax.ShapeDtypeStruct((4,), jnp.int32))
    assert _kernels(text) == {"latent_decode_attn"}
    assert '"latent_decode_attn/pallas_call"' in text
    assert 'decode_attention/jit(_latent_decode)"' in text
