"""Compiles for a described TPU v5e, with no chip (ADR-11).

The TPU compiler is installed here and compiles for a chip that is
described, not attached (`jax.experimental.topologies`).  That makes Mosaic
lowering of every Pallas kernel on the main path a property of the test
suite at the widths the chip runs — (8,128)/(16,128) tiling, scoped-VMEM
limits, `shard_map` typing — instead of something the first chip run
discovers.  A compile that passes is not a chip run: nothing executes and
nothing here says anything about results or speed.

All of it lives in this one file and loads libtpu only from the module-scoped
``topo`` fixture: the library belongs to one process, so every compile runs
in the test's own process (no child), and nothing touches the topology at
import or collection time.

The kernels gate on `jax.default_backend() == "tpu"`; from a CPU process the
``on_tpu`` fixture steers that check, so each compile takes exactly the
kernels and shape gates the chip would.
"""
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs to /tmp
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or it is held by another process
        pytest.skip("no v5e:2x2 topology can be described here: %s"
                    % str(e)[:200])


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """The kernels' backend gates see a TPU; no pin, so the shape gates
    decide as they would on the chip."""
    for pin in ("MXNET_FLASH_IMPL", "MXNET_FLASH_BSD_KERNEL", "MXNET_LN_IMPL",
                "MXNET_FLASH_LAYOUT", "MXNET_FLASH_BWD", "MXNET_CE_SHARD"):
        monkeypatch.delenv(pin, raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _compile(fn, *shapes):
    comp = jax.jit(fn).lower(*shapes).compile()
    return comp, comp.as_text().count("tpu_custom_call")


def _bf16(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=sharding)


# -- the one-chip kernels at the widths the chip runs ----------------------


@pytest.mark.parametrize("heads,head_dim", [(6, 128), (12, 64)])
def test_flash_hsd_fwd_bwd_at_flagship_width(on_tpu, one_chip, heads,
                                             head_dim):
    from mxnet_tpu.ops.pallas_kernels import flash_attention

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True)
                       .astype(jnp.float32))

    sh = _bf16((32, heads, 1024, head_dim), one_chip)
    _, kernels = _compile(jax.grad(loss, argnums=(0, 1, 2)), sh, sh, sh)
    assert kernels == 3  # forward, dq, dk/dv


@pytest.mark.parametrize("batch,seq,kernels_are", [
    (32, 1024, "loop"),     # whole K/V resident in VMEM
    (4, 8192, "stream"),    # past the residency cap: grid-streamed blocks
])
def test_flash_bsd_fwd_bwd_at_flagship_width(on_tpu, one_chip, batch, seq,
                                             kernels_are):
    from mxnet_tpu.ops.pallas_kernels import flash_attention_mod as fa

    q = jnp.zeros((batch, seq, 768), jnp.bfloat16)
    assert fa._bsd_structure(q, 6, seq) == kernels_are

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention_bsd(q, k, v, 6, causal=True)
                       .astype(jnp.float32))

    sh = _bf16((batch, seq, 768), one_chip)
    _, kernels = _compile(jax.grad(loss, argnums=(0, 1, 2)), sh, sh, sh)
    assert kernels == 3


@pytest.mark.parametrize("single_pass,kernels_expected", [
    ("1", 2),   # stats+residual forward, dW/db backward
    ("0", 3),   # 5-pass structure: forward, dx, dW/db
])
def test_fused_ce_fwd_bwd_at_flagship_width(on_tpu, one_chip, monkeypatch,
                                            single_pass, kernels_expected):
    from mxnet_tpu.ops.pallas_kernels import fused_softmax_ce

    monkeypatch.setenv("MXNET_CE_SINGLE_PASS", single_pass)
    n = d_vocab = 32768

    def loss(x, w, label):
        return jnp.sum(fused_softmax_ce(x, w, None, label))

    x = _bf16((n, 768), one_chip)
    w = _bf16((d_vocab, 768), one_chip)
    label = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    _, kernels = _compile(jax.grad(loss, argnums=(0, 1)), x, w, label)
    assert kernels == kernels_expected


def test_layer_norm_fwd_bwd_at_flagship_width(on_tpu, one_chip):
    from mxnet_tpu.ops.pallas_kernels.layer_norm import layer_norm

    def loss(x, g, b):
        return jnp.sum(layer_norm(x, g, b, 1e-5).astype(jnp.float32))

    x = _bf16((32768, 768), one_chip)
    g = jax.ShapeDtypeStruct((768,), jnp.float32, sharding=one_chip)
    _, kernels = _compile(jax.grad(loss, argnums=(0, 1, 2)), x, g, g)
    assert kernels == 2


# -- four described devices: kernels under shard_map ------------------------


def test_sharded_fused_ce_compiles_for_four_devices(on_tpu, topo):
    """The vocab-sharded head as `FusedSoftmaxCE` runs it on a 2x2 mesh:
    tokens over "data", vocabulary over "model", forward and backward."""
    from mxnet_tpu.ops.pallas_kernels.fused_ce import \
        fused_softmax_ce_sharded
    from mxnet_tpu.parallel.mesh import shard_map

    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    head = shard_map(
        lambda x, w, lbl: fused_softmax_ce_sharded(x, w, None, lbl, "model"),
        mesh=mesh, in_specs=(P("data", None), P("model", None), P("data")),
        out_specs=P("data"))

    def loss(x, w, lbl):
        return jnp.sum(head(x, w, lbl))

    n = vocab = 32768
    x = _bf16((n, 768), NamedSharding(mesh, P("data", None)))
    w = _bf16((vocab, 768), NamedSharding(mesh, P("model", None)))
    lbl = jax.ShapeDtypeStruct((n,), jnp.float32,
                               sharding=NamedSharding(mesh, P("data")))
    comp, kernels = _compile(jax.grad(loss, argnums=(0, 1)), x, w, lbl)
    assert kernels == 2
    # the lse reduce, the dx partials and the data-axis sum of dW ride the
    # mesh; nothing gathers the head
    txt = comp.as_text()
    assert "all-reduce" in txt and "all-gather(" not in txt


def test_ring_attention_compiles_for_four_devices(on_tpu, topo):
    from mxnet_tpu.parallel import ring_attention
    from mxnet_tpu.parallel.mesh import shard_map

    mesh = Mesh(np.array(topo.devices), ("seq",))
    spec = P(None, None, "seq")
    ring = shard_map(
        lambda q, k, v: ring_attention(q, k, v, "seq", causal=True),
        mesh=mesh, in_specs=(spec,) * 3, out_specs=spec)

    def loss(q, k, v):
        return jnp.sum(ring(q, k, v).astype(jnp.float32) ** 2)

    sh = _bf16((1, 6, 8192, 128), NamedSharding(mesh, spec))
    comp, kernels = _compile(jax.grad(loss, argnums=(0, 1, 2)), sh, sh, sh)
    assert kernels == 3
    assert "collective-permute" in comp.as_text()


def _sharded_serving_program(topo, program, n_blocks=256, heads=6):
    """A sub-mesh serving replica's prefill or decode program (`ServingEngine`
    with a Mesh) compiled for the four described devices, ``heads`` heads of
    128: params and the paged K/V pool sharded by the model's own rules, the
    trace scoped to the mesh as `ServingEngine._scoped` does.  (the compiled
    program, the model's depth, the bytes of one layer's K pool on one
    device)."""
    from mxnet_tpu.base import bfloat16
    from mxnet_tpu.parallel.mesh import MeshContext
    from mxnet_tpu.serving import TransformerKVModel

    L, V, S, E, bs = 2, 32768, 1024, 128 * heads, 16
    model = TransformerKVModel(V, S, num_layers=L, num_heads=heads,
                               num_embed=E, use_bias=False, dtype=bfloat16)
    mesh = Mesh(np.array(topo.devices), ("model",))
    repl = NamedSharding(mesh, P())
    shardings = model.param_shardings(mesh)
    params = {n: _bf16(s, shardings[n])
              for n, s in model.param_shapes().items()}
    kv = model.kv_shardings(mesh)[0]
    pool = _bf16((L, 2, n_blocks, bs, E), kv)

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=repl)

    if program == "prefill":
        def prog(params, pool, tokens, start, length, tables):
            with MeshContext(mesh):
                logits, pool = model.prefill_paged(params, pool, tokens,
                                                   start, length, tables)
            return jnp.argmax(logits, axis=-1), pool

        args = (ints(1, 128), ints(1), ints(1), ints(1, S // bs))
    else:
        def prog(params, pool, token, pos, tables):
            with MeshContext(mesh):
                logits, pool = model.decode_paged(params, pool, token, pos,
                                                  tables)
            return jnp.argmax(logits, axis=-1), pool

        args = (ints(8), ints(8), ints(8, S // bs))
    comp = jax.jit(prog, donate_argnums=(1,),
                   out_shardings=(repl, kv)).lower(params, pool,
                                                   *args).compile()
    return comp, L, n_blocks * bs * (E // mesh.size) * 2


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_sharded_serving_programs_compile_for_four_devices(on_tpu, topo,
                                                           program):
    """A sub-mesh serving replica's programs: with the trace scoped to the
    mesh the fused LayerNorm runs per device instead of being refused as not
    partitionable."""
    comp, layers, _ = _sharded_serving_program(topo, program)
    assert comp.as_text().count("tpu_custom_call") == 2 * layers + 1


def _serving_program(one_chip, what, n, n_blocks, layers=1, kv_quant=None):
    """`serve_decode_b<n>`, `serve_prefill_s<n>` or `serve_verify_b<n>` (four
    drafts a row) at GPT-2 large's widths, compiled for the described chip
    with the pool donated: (its text, its memory analysis, the bytes of one
    layer's K pool)."""
    from mxnet_tpu.base import bfloat16
    from mxnet_tpu.serving import TransformerKVModel

    V, S, E, bs = 512, 1024, 1280, 16
    model = TransformerKVModel(V, S, num_layers=layers, num_heads=20,
                               num_embed=E, dtype=bfloat16,
                               kv_quant=kv_quant)
    params = {n: _bf16(s, one_chip) for n, s in model.param_shapes().items()}
    shape = (layers, 2, n_blocks, bs, E)
    pool = _bf16(shape, one_chip)
    if kv_quant is not None:
        pool = (jax.ShapeDtypeStruct(shape, jnp.int8, sharding=one_chip),
                jax.ShapeDtypeStruct(shape[:-1], jnp.float32,
                                     sharding=one_chip))

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    if what == "decode":
        def prog(params, pool, token, pos, tables):
            logits, pool = model.decode_paged(params, pool, token, pos,
                                              tables)
            return jnp.argmax(logits, axis=-1), pool

        args = (ints(n), ints(n), ints(n, S // bs))
    else:
        step = {"prefill": model.prefill_paged,
                "verify": model.verify_paged}[what]

        def prog(params, pool, tokens, start, length, tables):
            logits, pool = step(params, pool, tokens, start, length, tables)
            return jnp.argmax(logits, axis=-1), pool

        rows, width = (1, n) if what == "prefill" else (n, 5)
        args = (ints(rows, width), ints(rows), ints(rows),
                ints(rows, S // bs))
    prog.__name__ = "serve_%s_%s%d" % (what, "s" if what == "prefill" else "b",
                                       n)
    comp = jax.jit(prog, donate_argnums=(1,)).lower(params, pool,
                                                    *args).compile()
    itemsize = 2 if kv_quant is None else 1
    return (comp.as_text(), comp.memory_analysis(),
            n_blocks * bs * E * itemsize)


def _op_names(text, program):
    return re.findall(r'op_name="jit\(%s\)/([^"]+)"' % program, text)


#: an expert layer's products, one Pallas call (`ops.moe.held_share`)
_EXPERT_CALL = "moe_experts/jit(_expert_ffn)/expert_ffn/pallas_call"


_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s32": 4,
             "u32": 4, "f32": 4}


def _assert_pool_is_only_updated_in_place(text, k_pool_bytes, n_blocks):
    """No instruction of the entry computation makes a value the size of one
    layer's K pool, or a layer's slice of anything laid out by block (an
    int8 pool's scales are a 320th of its rows), but the pool's own
    parameters and the in-place `kv_scatter` of the donated pool: a fusion
    named after the scatter, or one whose root is the scatter (the compiler
    fuses a one-block chunk's projection into the update and names the fusion
    after the matmul).  Tuples and bitcasts make no value.  (The scales whole
    are laid out anew on the way in and on the way out, before and after
    this PR: the device keeps their 16-wide rows with the blocks minor.)"""
    entry = text[text.index("\nENTRY "):]
    for m in re.finditer(r"\n\s+(?:ROOT )?%\S+ = (.*?) ([\w-]+)\((.*)", entry):
        types, opcode, rest = m.groups()
        if opcode in ("parameter", "tuple", "get-tuple-element", "bitcast"):
            continue
        for dtype, dims in re.findall(r"(\w+)\[([\d,]+)\]", types):
            dims = [int(d) for d in dims.split(",")]
            if n_blocks in dims[:2] or np.prod(dims) * _ITEMSIZE[dtype] \
                    >= k_pool_bytes:
                break
        else:
            continue
        called = re.search(r"calls=(%[\w.-]+)", rest)
        root = re.search(r"\n%s .*?\n\s+ROOT ([^\n]*)" % re.escape(
            called.group(1)), text, re.S).group(1) if called else ""
        assert "kv_scatter/scatter" in rest or "kv_scatter/scatter" in root, \
            m.group(0)[:300]


@pytest.mark.parametrize("path", ["kernel", "jnp_int8_pool"])
def test_decode_pool_is_read_as_the_tpu_compiler_names_it(on_tpu, one_chip,
                                                          path):
    """How a decode launch reads the pool, in the compiled program's own
    names, at GPT-2 large's widths (PERF.md, PR 25, PR 26 and PR 31).

    kernel: the bf16 pool goes to one `paged_decode_attn` custom call a
    layer, under `decode_attention`, whole: nothing gathers it, no
    operation makes a value the size of a layer's K pool but the in-place
    `kv_scatter` of the donated pool, and the program's temporaries are
    smaller than that.  It compiles for every decode bucket of the cell.

    jnp_int8_pool: the quantised pool keeps `gather_paged_kv` +
    `decode_attention`.  One gather a layer and K or V reads the table's
    blocks out of the whole pool, and one their scales, all under
    `kv_gather`.  (Until PR 31 the layer's K pool was sliced out first; the
    TPU compiler materialised the slice, `slice_bitcast_fusion` under the
    name `kv_gather/squeeze`: a copy of a layer's pool before every gather,
    a quarter of a decode launch on the chip before the kernel and three
    quarters of a prefill chunk.)"""
    if path == "jnp_int8_pool":
        text, _, _ = _serving_program(one_chip, "decode", 4, 64,
                                      kv_quant="int8")
        names = set(_op_names(text, "serve_decode_b4"))
        assert "tpu_custom_call" in text        # the fused LayerNorms
        assert not any("paged_decode_attn" in n for n in names)
        # (`_gather_ctx` dequantizes under the scope `gather_paged_kv`
        # gathers under: the gather's name holds it twice)
        assert {"kv_gather/kv_gather/gather", "kv_gather/mul",
                "kv_scatter/scatter"} <= names
        # no slice of the pool, and none of the pool's own operations
        # outside its scopes
        assert not any(n.endswith("/squeeze") for n in names)
        assert not names & {"squeeze", "gather", "scatter", "dynamic_slice",
                            "dynamic_update_slice"}
        return
    layers, n_blocks = 2, 3600
    for batch in (1, 2, 4, 8, 16, 32, 64):
        program = "serve_decode_b%d" % batch
        text, memory, k_pool_bytes = _serving_program(
            one_chip, "decode", batch, n_blocks, layers)
        names = _op_names(text, program)
        calls = [n for n in names if n.endswith("/pallas_call")
                 and "paged_decode_attn" in n]
        assert calls == ["decode_attention/jit(_paged_decode)/"
                         "paged_decode_attn/pallas_call"] * layers
        # the layers call one lowered function: the kernel is traced and
        # lowered once a program, whatever the model's depth
        assert text.count("tpu_custom_call") == 3 * layers + 1
        assert not any("kv_gather" in n for n in names)
        # the pool is donated and updated in place ...
        assert "input_output_alias" in text.splitlines()[0]
        assert memory.alias_size_in_bytes == 2 * layers * k_pool_bytes
        # ... and nothing the size of one layer's K pool is made beside it
        assert memory.temp_size_in_bytes < k_pool_bytes
        _assert_pool_is_only_updated_in_place(text, k_pool_bytes, n_blocks)


@pytest.mark.parametrize("case", [
    "prefill_s16", "prefill_s32", "prefill_s64", "prefill_s128",
    "prefill_s256", "prefill_s512", "verify_b4", "int8_decode_b4",
    "int8_prefill_s512", "sharded_decode_b8"])
def test_jnp_paths_read_the_table_from_the_pool_in_place(on_tpu, topo,
                                                         one_chip, case):
    """Every program that reads a row's context from the pool (the serving
    cell's six prefill chunks, through `paged_prefill_attn` since PR 35; the
    speculative verify launch, the int8 pool's decode and prefill and the
    tensor-sharded engine's decode, with `gather_paged_kv`), at 2 layers and
    3,600 blocks: the pool (and the int8 pool's scales) is donated and
    aliased in and out, the temporaries are under one layer's K pool, and
    nothing of that size is made but by the in-place `kv_scatter`.

    Until PR 31 each of them sliced the layer's K and V pools out before the
    gather, and the TPU compiler made the slices: 2 x 147 MB of temporaries
    at these sizes, 10.6 GB read and written a chunk at GPT-2 large's 36
    layers, 32 of a chunk's 42 ms on the chip (PERF.md, PR 31)."""
    layers, n_blocks = 2, 3600
    if case == "sharded_decode_b8":
        # (8 heads: a device's 2 are whole tiles of 128 lanes.  The 6 heads
        # of the program above leave it 192 lanes, and the device then keeps
        # the pool blocks-minor and the program lays all of it out anew on
        # the way in and out, before and after PR 31: ROADMAP.md S3)
        comp, _, k_pool_bytes = _sharded_serving_program(topo, "decode",
                                                         n_blocks, heads=8)
        text, memory = comp.as_text(), comp.memory_analysis()
        aliased = 2 * layers * k_pool_bytes
    else:
        kv_quant = "int8" if case.startswith("int8_") else None
        program = case.removeprefix("int8_")
        what, n = program.split("_")
        text, memory, k_pool_bytes = _serving_program(
            one_chip, what, int(n[1:]), n_blocks, layers, kv_quant)
        aliased = 2 * layers * k_pool_bytes
        if kv_quant is not None:    # a float32 scale a row of 1,280 int8
            aliased += aliased // 1280 * 4
        names = _op_names(text, "serve_" + program)
        kernel = what == "prefill" and kv_quant is None
        assert any(("paged_prefill_attn" if kernel else "kv_gather") in n
                   for n in names)
        assert kernel != any("kv_gather" in n for n in names)
    assert "input_output_alias" in text.splitlines()[0]
    # (at least: the compiler pads the scales' and the shards' tiles)
    assert memory.alias_size_in_bytes >= aliased
    assert memory.temp_size_in_bytes < k_pool_bytes
    _assert_pool_is_only_updated_in_place(text, k_pool_bytes, n_blocks)


def test_megastep_scan_reads_the_pool_through_the_kernel(on_tpu, one_chip):
    """`serve_mega_b*` scans `decode_paged`'s body with the pool as the
    carry (so does the speculative drafter): the kernel lowers inside the
    scan, once a layer, and the carried pool is still donated and never
    copied."""
    from mxnet_tpu.base import bfloat16
    from mxnet_tpu.serving import TransformerKVModel

    layers, n_blocks, batch = 2, 3600, 8
    V, S, E, bs = 512, 1024, 1280, 16
    model = TransformerKVModel(V, S, num_layers=layers, num_heads=20,
                               num_embed=E, dtype=bfloat16)
    params = {n: _bf16(s, one_chip) for n, s in model.param_shapes().items()}
    pool = _bf16((layers, 2, n_blocks, bs, E), one_chip)

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    def serve_mega_b8(params, pool, token, pos, left, eos, tables):
        return model.decode_megastep(
            params, pool, token, pos, left, eos, tables, 4,
            lambda logits, newpos: jnp.argmax(logits, -1).astype(jnp.int32))

    comp = jax.jit(serve_mega_b8, donate_argnums=(1,)).lower(
        params, pool, ints(batch), ints(batch), ints(batch), ints(batch),
        ints(batch, S // bs)).compile()
    text, memory = comp.as_text(), comp.memory_analysis()
    k_pool_bytes = n_blocks * bs * E * 2
    assert text.count("paged_decode_attn/pallas_call") == layers
    assert "kv_gather" not in text
    assert memory.alias_size_in_bytes == 2 * layers * k_pool_bytes
    assert memory.temp_size_in_bytes < k_pool_bytes


# -- the latent pool's programs at the served widths -------------------------


def _latent_model(layers):
    """Kimi-K2.5's widths, this chip's share (12 of 384 experts, an eighth
    of the vocabulary), ``layers`` deep: one dense layer, the rest expert
    layers."""
    from mxnet_tpu.base import bfloat16
    from mxnet_tpu.serving import LatentMoEKVModel

    return LatentMoEKVModel(
        20480, 16384, layers, 7168, 64, 1536, 512, 128, 64, 128, 18432,
        2048, 384, (0, 12), 8, first_dense=1, routed_scaling_factor=2.827,
        rope_theta=50000.0, rope_scaling=dict(
            beta_fast=32, beta_slow=1, factor=64, mscale=1, mscale_all_dim=1,
            original_max_position_embeddings=4096), dtype=bfloat16)


def _latent_program(one_chip, what, n, layers=2, n_blocks=4000, bs=64):
    """`serve_decode_b<n>` or `serve_prefill_s<n>` of the latent model,
    compiled for the described chip with the pool donated: (its text, its
    memory analysis, the bytes of one layer of the pool)."""
    model = _latent_model(layers)
    params = {k: _bf16(v, one_chip) for k, v in model.param_shapes().items()}
    pool = _bf16((layers, n_blocks, bs, model.pool_width), one_chip)

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    tables = ints(n if what == "decode" else 1, model.seq_len // bs)
    if what == "decode":
        def prog(params, pool, token, pos, tables):
            logits, pool = model.decode_paged(params, pool, token, pos,
                                              tables)
            return jnp.argmax(logits, axis=-1), pool

        args = (ints(n), ints(n), tables)
    else:
        def prog(params, pool, tokens, start, length, tables):
            logits, pool = model.prefill_paged(params, pool, tokens, start,
                                               length, tables)
            return jnp.argmax(logits, axis=-1), pool

        args = (ints(1, n), ints(1), ints(1), tables)
    prog.__name__ = "serve_%s_%s%d" % (what, "b" if what == "decode" else "s",
                                       n)
    comp = jax.jit(prog, donate_argnums=(1,)).lower(params, pool,
                                                    *args).compile()
    return (comp.as_text(), comp.memory_analysis(),
            n_blocks * bs * model.pool_width * 2)


@pytest.mark.parametrize("batch", [1, 8, 64])
def test_latent_decode_reads_the_pool_through_its_kernel(on_tpu, one_chip,
                                                         batch):
    """A decode launch over the latent pool at Kimi-K2.5's widths: one
    `latent_decode_attn` custom call a layer under `decode_attention`, from
    one lowered function, beside the expert layer's `expert_ffn`; the pool
    donated and updated in place; and no temporary that grows with the pool
    or with rows x table width x 576 (the gathered context the `jax.numpy`
    body would make)."""
    layers = 2
    program = "serve_decode_b%d" % batch
    text, memory, layer_bytes = _latent_program(one_chip, "decode", batch,
                                                layers)
    calls = [n for n in _op_names(text, program)
             if n.endswith("/pallas_call")]
    # (the compiler names the copies it puts around a call after it too)
    assert set(calls) == {"decode_attention/jit(_latent_decode)/"
                          "latent_decode_attn/pallas_call", _EXPERT_CALL}
    # one dense layer, the others expert layers
    assert text.count("tpu_custom_call") == layers + layers - 1
    assert "input_output_alias" in text.splitlines()[0]
    assert memory.alias_size_in_bytes == layers * layer_bytes
    gathered = batch * 16384 * 576 * 2
    assert memory.temp_size_in_bytes < min(layer_bytes, gathered)


@pytest.mark.parametrize("chunk,layers", [(512, 2), (64, 4)])
def test_latent_prefill_holds_no_table_wide_scores(on_tpu, one_chip, chunk,
                                                   layers):
    """A chunk over a table of 16,384 positions at Kimi-K2.5's widths: one
    `latent_prefill_attn` custom call a layer under `mla_prefill_attention`,
    from one lowered function, and no loop around it (nor around the expert
    layer's products, one `expert_ffn` call); the kernel keeps a
    step's scores in VMEM, so the program's temporaries are far from the
    2.1 GB that float32 scores of 512 x table width x heads would take (or
    the 0.7 GB of the whole context expanded to per-head keys and values),
    and under one layer of the pool.  The one-block chunk at four layers is
    the case in which the TPU compiler once laid the whole pool out anew to
    suit a whole-block scatter (a dynamic-update-slice to it) and copied it:
    the chunk's rows are scattered row by row since (my chip run, PR 30)."""
    text, memory, layer_bytes = _latent_program(one_chip, "prefill", chunk,
                                                layers)
    assert "input_output_alias" in text.splitlines()[0]
    assert memory.alias_size_in_bytes == layers * layer_bytes
    assert memory.temp_size_in_bytes < layer_bytes
    names = _op_names(text, "serve_prefill_s%d" % chunk)
    calls = [n for n in names if n.endswith("/pallas_call")]
    assert set(calls) == {"mla_prefill_attention/jit(_latent_prefill)/"
                          "latent_prefill_attn/pallas_call", _EXPERT_CALL}
    assert text.count("tpu_custom_call") == layers + layers - 1
    assert not any("mla_prefill_loop" in n or "moe_loop" in n
                   for n in names)


# -- whole train steps (toy widths: seconds each) ---------------------------


def _lm_trainer(mesh, attn_layout="bhsd", fused=False, heads=2, batch=4):
    from mxnet_tpu import models
    from mxnet_tpu.base import bfloat16
    from mxnet_tpu.parallel import SPMDTrainer

    B, S, D, V = batch, 512, 256, 2048
    net = models.get_transformer_lm(
        vocab_size=V, seq_len=S, num_layers=1, num_heads=heads, num_embed=D,
        fused_head=fused, attn_layout=attn_layout)
    return SPMDTrainer(net, mesh,
                       data_shapes={"data": (B, S), "softmax_label": (B, S)},
                       lr=1e-3, optimizer="adam", dtype=bfloat16,
                       adam_v_dtype="bfloat16", abstract=True)


# The head-split marker: the bf16 (B, H, S, d) activation shape.
# Activations are always bf16 in these builds, so this is the shape a
# regressed head split would reappear in.  (The f32 lse shares the
# (B, H, S, 128) shape legitimately, so an any-dtype check would false-
# positive; symbol names do not survive into optimized-HLO op_name
# metadata, so a name check is not available.)
_HEAD_SPLIT_SHAPE = "bf16[4,2,512,128]"


def test_aot_compiles_hsd_kernels(on_tpu, topo):
    mesh = Mesh(np.array(topo.devices[:1]), ("data",))
    comp = _lm_trainer(mesh).lower_step(batch_dtypes={"data": "int32"})
    txt = comp.as_text()
    assert "tpu_custom_call" in txt  # Pallas kernels really lowered
    # canary for the bsd test's negative assertion: this really is how
    # head-split modules print the activation shape
    assert _HEAD_SPLIT_SHAPE in txt
    ca = comp.cost_analysis()
    ca = ca[0] if isinstance(ca, (list, tuple)) else ca
    assert ca.get("bytes accessed", 0) > 0


def test_aot_compiles_bsd_loop_kernels(on_tpu, topo):
    mesh = Mesh(np.array(topo.devices[:1]), ("data",))
    comp = _lm_trainer(mesh, attn_layout="bsd").lower_step(
        batch_dtypes={"data": "int32"})
    txt = comp.as_text()
    assert "tpu_custom_call" in txt
    # the transposeless property: no bf16 head-split activation anywhere
    # in the lowered module
    assert _HEAD_SPLIT_SHAPE not in txt


def test_aot_compiles_bsd_stream_kernels(on_tpu, topo, monkeypatch):
    monkeypatch.setenv("MXNET_FLASH_BSD_KERNEL", "stream")
    mesh = Mesh(np.array(topo.devices[:1]), ("data",))
    comp = _lm_trainer(mesh, attn_layout="bsd", fused=True).lower_step(
        batch_dtypes={"data": "int32"})
    assert "tpu_custom_call" in comp.as_text()


@pytest.mark.parametrize("shard_head", ["0", "1"])
def test_aot_compiles_dp_tp_step_for_four_devices(on_tpu, topo, monkeypatch,
                                                  shard_head):
    """The whole step on a 2x2 mesh: GSPMD cannot partition a Mosaic
    kernel, so LayerNorm, attention and the replicated head each run per
    device under shard_map (`_spmd.call_local`), the vocab-sharded head
    under its own."""
    monkeypatch.setenv("MXNET_CE_SHARD", shard_head)
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    comp = _lm_trainer(mesh, attn_layout="bsd", fused=True).lower_step(
        batch_dtypes={"data": "int32"})
    # 3 LayerNorms fwd+bwd, attention fwd + 2 bwd, head fwd + dW
    assert comp.as_text().count("tpu_custom_call") == 11


def test_abstract_trainer_refuses_lower_without_abstract():
    from mxnet_tpu import models
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh

    net = models.get_transformer_lm(vocab_size=64, seq_len=64)
    tr = SPMDTrainer(net, make_mesh(shape=(1,), axis_names=("data",)),
                     data_shapes={"data": (2, 64),
                                  "softmax_label": (2, 64)})
    with pytest.raises(MXNetError, match="abstract"):
        tr.lower_step()


# -- the grouped-query pool and the conv state at the served widths ----------


def _shortconv_program(one_chip, what, n, kinds, n_blocks=4609, bs=64,
                       slots=65):
    """`serve_decode_b<n>` or `serve_prefill_s<n>` of the LFM2-MoE block at
    its published widths over ``kinds`` layers (the first two dense),
    compiled for the described chip with (pool, state) donated: (its text,
    its memory analysis, the model)."""
    from mxnet_tpu.base import bfloat16
    from mxnet_tpu.serving import ShortConvMoEKVModel

    model = ShortConvMoEKVModel(65536, 4608, kinds, 2048, 32, 8, 3, 7168,
                                1792, 32, 4, num_dense_layers=2,
                                dtype=bfloat16)
    params = {k: _bf16(v, one_chip) for k, v in model.param_shapes().items()}
    cache = (_bf16((model.attn_layers, 2, n_blocks, bs, model.kv_width),
                   one_chip),
             _bf16((model.conv_layers, slots, 2, model.hidden), one_chip))

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    if what == "decode":
        def prog(params, cache, token, pos, tables, slots):
            logits, cache = model.decode_paged(params, cache, token, pos,
                                               tables, slots=slots)
            return jnp.argmax(logits, axis=-1), cache

        args = (ints(n), ints(n), ints(n, model.seq_len // bs), ints(n))
    else:
        def prog(params, cache, tokens, start, length, tables, slots):
            logits, cache = model.prefill_paged(params, cache, tokens, start,
                                                length, tables, slots=slots)
            return jnp.argmax(logits, axis=-1), cache

        args = (ints(1, n), ints(1), ints(1), ints(1, model.seq_len // bs),
                ints(1))
    prog.__name__ = "serve_%s_%s%d" % (what, "b" if what == "decode" else "s",
                                       n)
    comp = jax.jit(prog, donate_argnums=(1,)).lower(params, cache,
                                                    *args).compile()
    return comp.as_text(), comp.memory_analysis(), model


KINDS = ["conv", "conv", "full_attention", "conv", "conv", "conv",
         "full_attention", "conv"]


@pytest.mark.parametrize("batch", [1, 64])
def test_grouped_query_decode_reads_the_pool_through_the_kernel(
        on_tpu, one_chip, monkeypatch, batch):
    """A decode launch over the 8-head pool under 32 query heads, at the
    served widths: one `paged_decode_attn` custom call an attention layer
    under `decode_attention`, from one lowered function, and an `expert_ffn`
    call an expert layer; pool and state donated and updated in place; no
    temporary that grows with the pool or with rows x table width x 512 (the
    gathered context the `jax.numpy` body would make)."""
    from mxnet_tpu.ops.pallas_kernels import paged_attention

    monkeypatch.setattr(paged_attention, "_INTERPRET", False)
    program = "serve_decode_b%d" % batch
    text, memory, model = _shortconv_program(one_chip, "decode", batch,
                                             KINDS)
    assert model.paged_decode_kernel(
        (jax.ShapeDtypeStruct((2, 2, 4609, 64, 512), jnp.bfloat16), None))
    calls = [n for n in _op_names(text, program)
             if n.endswith("/pallas_call")]
    assert set(calls) == {"decode_attention/jit(_paged_decode)/"
                          "paged_decode_attn/pallas_call", _EXPERT_CALL}
    assert text.count("tpu_custom_call") == model.attn_layers + 6 == 8
    assert "input_output_alias" in text.splitlines()[0]
    pool_bytes = 2 * 2 * 4609 * 64 * 512 * 2
    state_bytes = 6 * 65 * 2 * 2048 * 2
    assert memory.alias_size_in_bytes == pool_bytes + state_bytes
    gathered = batch * 4608 * 512 * 2
    assert memory.temp_size_in_bytes < min(pool_bytes // 2, 64 * gathered)
    scopes = {part for n in _op_names(text, program)
              for part in n.split("/")}
    assert {"short_conv", "conv_state", "qk_norm", "rope", "moe_experts",
            "kv_scatter"} <= scopes


def _f32_scores(text, c, width):
    """The float32 values of the program whose shape holds both the chunk's
    ``c`` and the table's ``width``: scores of the chunk against the whole
    table."""
    return [d for d in re.findall(r"f32\[([\d,]+)\]", text)
            if {c, width} <= {int(x) for x in d.split(",")}]


@pytest.mark.parametrize("model", ["gpt2-large", "lfm2"])
def test_prefill_chunk_attends_through_the_paged_kernel(on_tpu, one_chip,
                                                        monkeypatch, model):
    """`serve_prefill_s512` at the two K/V-pair cells' widths (GPT-2 large's
    20 heads of 64 over tables of 1,024 positions; LFM2's 32 query heads
    over 8 K/V heads, tables of 4,608): one `paged_prefill_attn` custom call
    an attention layer under `chunk_attention`, from one lowered function;
    nothing gathers the table (`kv_gather`), and no value holds float32
    scores of the chunk against the table's width (what `chunk_attention`
    made: 42 MB a layer for GPT-2, 302 MB for LFM2)."""
    from mxnet_tpu.ops.pallas_kernels import paged_attention

    monkeypatch.setattr(paged_attention, "_INTERPRET", False)
    if model == "lfm2":
        text, memory, m = _shortconv_program(one_chip, "prefill", 512, KINDS)
        layers, width, scores = m.attn_layers, 4608, 512 * 4608 * 32 * 4
        assert m.paged_prefill_kernel(
            (jax.ShapeDtypeStruct((2, 2, 4609, 64, 512), jnp.bfloat16),
             None), 512)
    else:
        text, memory, _ = _serving_program(one_chip, "prefill", 512, 3600, 2)
        layers, width, scores = 2, 1024, 512 * 1024 * 20 * 4
    names = _op_names(text, "serve_prefill_s512")
    calls = [n for n in names if n.endswith("/pallas_call")
             and "paged_prefill_attn" in n]
    assert calls == ["chunk_attention/jit(_paged_prefill)/"
                     "paged_prefill_attn/pallas_call"] * layers
    assert not any("kv_gather" in n for n in names)
    assert "input_output_alias" in text.splitlines()[0]
    assert not _f32_scores(text, 512, width)
    assert memory.temp_size_in_bytes < scores


def test_a_chunk_over_the_grouped_query_pool_copies_no_pool(on_tpu,
                                                            one_chip):
    """A 512-token chunk at the served widths: the chunk's K and V rows are
    scattered by (block, offset) and the pool is donated, so the program's
    temporaries are never a copy of the pool (1.2 GB a layer here).  (Until
    PR 35 they held the float32 scores of 512 x the table's width x 32
    heads, 0.3 GB a layer.)"""
    text, memory, model = _shortconv_program(one_chip, "prefill", 512, KINDS)
    assert "input_output_alias" in text.splitlines()[0]
    layer_pool = 2 * 4609 * 64 * 512 * 2
    assert memory.alias_size_in_bytes >= 2 * layer_pool
    assert memory.temp_size_in_bytes < 0.6 * layer_pool


# -- the window/full hybrid at the served widths ------------------------------


def _mimo_program(one_chip, what, n, kinds=(0, 1), n_blocks=2049, bs=64,
                  slots=65):
    """`serve_decode_b<n>` or `serve_prefill_s<n>` of the MiMo-V2 block at
    its published widths (this chip's 16 of 256 experts, an eighth of the
    vocabulary) over ``kinds`` layers (0 full, 1 window; the first dense),
    tables of 16,384 positions, compiled for the described chip with (pool,
    ring) donated: (its text, its memory analysis, the model)."""
    from mxnet_tpu.base import bfloat16
    from mxnet_tpu.serving import HybridWindowMoEKVModel

    model = HybridWindowMoEKVModel(
        19072, 16384, kinds, (0,) + (1,) * (len(kinds) - 1), 4096, 64, 4, 8,
        192, 128, 64, 128, 16384, 2048, 256, (0, 16), 8, rope_theta=1e7,
        window_rope_theta=1e4, value_scale=0.707, dtype=bfloat16)
    params = {k: _bf16(v, one_chip) for k, v in model.param_shapes().items()}
    shape = (model.full_layers, 1, n_blocks, bs)
    rings = (model.ring_layers, slots, model.window, model.window_kv_heads)
    cache = ((_bf16(shape + (model.k_width,), one_chip),
              _bf16(shape + (model.v_width,), one_chip)),
             (_bf16(rings[:3] + (rings[3] * model.head_dim,), one_chip),
              _bf16(rings[:3] + (rings[3] * model.v_head_dim,), one_chip)))

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    if what == "decode":
        def prog(params, cache, token, pos, tables, slots):
            logits, cache = model.decode_paged(params, cache, token, pos,
                                               tables, slots=slots)
            return jnp.argmax(logits, axis=-1), cache

        args = (ints(n), ints(n), ints(n, model.seq_len // bs), ints(n))
    else:
        def prog(params, cache, tokens, start, length, tables, slots):
            logits, cache = model.prefill_paged(params, cache, tokens, start,
                                                length, tables, slots=slots)
            return jnp.argmax(logits, axis=-1), cache

        args = (ints(1, n), ints(1), ints(1), ints(1, model.seq_len // bs),
                ints(1))
    prog.__name__ = "serve_%s_%s%d" % (what, "b" if what == "decode" else "s",
                                       n)
    comp = jax.jit(prog, donate_argnums=(1,)).lower(params, cache,
                                                    *args).compile()
    return comp.as_text(), comp.memory_analysis(), model


@pytest.mark.parametrize("batch", [8, 64])
def test_the_hybrid_decode_reads_its_full_layers_through_the_kernel(
        on_tpu, one_chip, monkeypatch, batch):
    """A decode launch at MiMo-V2's widths: one `paged_decode_attn` custom
    call a full layer under `decode_attention`, at q/k 192 and v 128 with 16
    query heads a K/V head, from one lowered function, and an `expert_ffn`
    call an expert layer; the window layer attends over its ring in
    `jax.numpy`; pool and rings donated and updated
    in place; no temporary that grows with the pool or with rows x table
    width x the K/V widths (the gathered context the `jax.numpy` body would
    make)."""
    from mxnet_tpu.ops.pallas_kernels import paged_attention

    monkeypatch.setattr(paged_attention, "_INTERPRET", False)
    program = "serve_decode_b%d" % batch
    text, memory, model = _mimo_program(one_chip, "decode", batch,
                                        kinds=(0, 1, 1))
    calls = [n for n in _op_names(text, program)
             if n.endswith("/pallas_call")]
    assert set(calls) == {"decode_attention/jit(_paged_decode)/"
                          "paged_decode_attn/pallas_call", _EXPERT_CALL}
    assert text.count("tpu_custom_call") == model.full_layers + 2 == 3
    assert "input_output_alias" in text.splitlines()[0]
    pool_bytes = 2049 * 64 * (768 + 512) * 2
    ring_bytes = 2 * 65 * 128 * 8 * 320 * 2
    assert memory.alias_size_in_bytes == pool_bytes + ring_bytes
    gathered = batch * 16384 * (768 + 512) * 2
    assert memory.temp_size_in_bytes < min(pool_bytes // 2, gathered)
    # the rings are gathered by row and written in place: no value of the
    # program is the size of the rings (the compiler laid the whole of them
    # out anew in every layer, 10.8 ms a launch on a v5e, while K and V were
    # lanes of one array; at a bucket of one row it still lays them out once
    # a launch, and the cell's buckets start at 8)
    entry = text[text.index("\nENTRY "):]
    laid_out = [m.group(0)[:200] for m in re.finditer(
        r"\n\s+(?:ROOT )?%\S+ = \(?bf16\[2,65,128,\d+\][^\n]*", entry)
        if not re.search(r" (parameter|bitcast|get-tuple-element)\(",
                         m.group(0))
        and "window_state/scatter" not in m.group(0)]
    assert not laid_out, laid_out
    scopes = {part for n in _op_names(text, program)
              for part in n.split("/")}
    assert {"window_attention", "window_state", "rope", "moe_experts",
            "kv_scatter", "lm_head"} <= scopes


def _table_wide_scores(text, c, width):
    """The float32 values of three axes or more whose shape holds both the
    chunk's ``c`` and the table's ``width``: scores of the chunk against
    the whole table, a head or a group apart (a dense layer's (c,
    intermediate) product is two axes, at a width that may equal the
    table's)."""
    return [d for d in re.findall(r"f32\[([\d,]+)\]", text)
            if d.count(",") >= 2
            and {c, width} <= {int(x) for x in d.split(",")}]


@pytest.mark.parametrize("chunk", [512, 64])
def test_the_hybrid_prefill_holds_no_table_wide_scores(on_tpu, one_chip,
                                                       chunk):
    """A chunk over a table of 16,384 positions at MiMo-V2's widths: the full
    layer's attention walks the table a step of 1,024 positions at a time
    (`paged_chunk_stream`, a loop under `chunk_attention`), so no value
    holds float32 scores of the chunk against the table's width (2.1 GB at
    64 heads x 512, what `chunk_attention` over the gathered table would
    make), and the temporaries stay under a layer of the pool; the window
    layer's band is under `window_attention`."""
    program = "serve_prefill_s%d" % chunk
    text, memory, _ = _mimo_program(one_chip, "prefill", chunk)
    assert "input_output_alias" in text.splitlines()[0]
    assert not _table_wide_scores(text, chunk, 16384)
    names = _op_names(text, program)
    # (no attention kernel: the expert layer's is the only one)
    assert not any("kv_gather" in n or "pallas_call" in n
                   and not n.startswith(_EXPERT_CALL) for n in names)
    assert any(n.startswith("chunk_attention/while") for n in names)
    assert any("window_attention" in n for n in names)
    assert memory.temp_size_in_bytes < 64 * 512 * 16384 * 4 // 4


# -- the expert layers at the served widths ----------------------------------


def _kernel_calls(text, program):
    """The op names of the program's Mosaic kernels, one a call."""
    return [m.group(1) for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
            for m in [re.search(r'op_name="jit\(%s\)/([^"]+)"' % program,
                                line)] if m]


@pytest.mark.parametrize("model,what,n", [
    ("lfm2", "decode", 64), ("lfm2", "prefill", 512),
    ("kimi", "decode", 64), ("kimi", "prefill", 512)])
def test_each_expert_layer_is_one_kernel_call(on_tpu, one_chip, model, what,
                                              n):
    """LFM2's and Kimi's `serve_decode_b64` and `serve_prefill_s512` at the
    cells' widths: one `expert_ffn` call an expert layer under
    `moe_experts`, from one lowered function, and no `moe_loop` (the
    `fori_loop` of a step a tile that the kernel takes the place of).  The
    compile passing is Mosaic's check that the rows, the accumulator and
    the weights' two slots fit the VMEM the kernel asks for."""
    from mxnet_tpu.ops.pallas_kernels import expert_ffn as ef

    if model == "lfm2":
        text = _shortconv_program(one_chip, what, n, KINDS)[0]
        experts, d, f, block = 6, 2048, 1792, 1792
    else:
        text = _latent_program(one_chip, what, n)[0]
        experts, d, f, block = 1, 7168, 2048, 512
    program = "serve_%s_%s%d" % (what, "b" if what == "decode" else "s", n)
    calls = [c for c in _kernel_calls(text, program) if "expert_ffn" in c]
    assert calls == [_EXPERT_CALL] * experts
    assert not any("moe_loop" in c for c in _op_names(text, program))
    # an LFM2 expert's three matrices stream whole, a Kimi one's in four
    assert ef.ffn_block(d, f, 2) == block
    assert ef._vmem_bytes(n, d, block, 2) <= ef._VMEM_BYTES


# -- the programs of the other served models, unchanged ------------------------


def _digest(text):
    """A digest of a lowered program's text, each Mosaic kernel's
    serialised body replaced by a digest of its operations without the
    source locations the body carries (a line moved in a kernel's file
    changes the body's bytes and not the kernel)."""
    import base64
    import hashlib
    import json

    from jaxlib.mlir import ir

    def kernel(m):
        cfg = json.loads(m.group(1).replace("\\22", '"'))
        body = base64.b64decode(cfg["custom_call_config"]["body"])
        ctx = ir.Context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            asm = ir.Module.parse(body).operation.get_asm(
                enable_debug_info=False)
        return 'backend_config = "%s"' % hashlib.sha256(
            asm.encode()).hexdigest()

    return hashlib.sha256(re.sub(
        r'backend_config = "((?:[^"\\]|\\.)*)"', kernel,
        text).encode()).hexdigest()[:16]


def _lowered(one_chip, model, cache, what, n, slots=False):
    """The text of `serve_decode_b<n>` or `serve_prefill_s<n>` of ``model``
    over ``cache`` (shapes), lowered for the described chip."""
    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    params = {k: _bf16(v, one_chip) for k, v in model.param_shapes().items()}
    tail = {"slots": ints(n if what == "decode" else 1)} if slots else {}
    width = model.seq_len // 64
    if what == "decode":
        def prog(params, cache, token, pos, tables, **kw):
            logits, cache = model.decode_paged(params, cache, token, pos,
                                               tables, **kw)
            return jnp.argmax(logits, axis=-1), cache

        args = (ints(n), ints(n), ints(n, width))
    else:
        def prog(params, cache, tokens, start, length, tables, **kw):
            logits, cache = model.prefill_paged(params, cache, tokens, start,
                                                length, tables, **kw)
            return jnp.argmax(logits, axis=-1), cache

        args = (ints(1, n), ints(1), ints(1), ints(1, width))
    prog.__name__ = "serve_%s_%s%d" % (what, "b" if what == "decode" else "s",
                                       n)
    return jax.jit(prog, donate_argnums=(1,)).lower(params, cache, *args,
                                                    **tail).as_text()


def _todays_model(one_chip, which):
    """(model, its cache's shapes, whether it takes slots) at the cells'
    widths, two or three layers deep."""
    from mxnet_tpu.base import bfloat16
    from mxnet_tpu.serving import (HybridWindowMoEKVModel,
                                   ShortConvMoEKVModel, TransformerKVModel)

    if which == "gpt2":
        model = TransformerKVModel(50257, 1024, num_layers=2, num_heads=20,
                                   num_embed=1280, dtype=bfloat16)
        return model, _bf16((2, 2, 513, 64, 1280), one_chip), False
    if which == "lfm2":
        model = ShortConvMoEKVModel(65536, 4608, KINDS[:3], 2048, 32, 8, 3,
                                    7168, 1792, 32, 4, num_dense_layers=2,
                                    dtype=bfloat16)
        return model, (_bf16((1, 2, 513, 64, 512), one_chip),
                       _bf16((2, 65, 2, 2048), one_chip)), True
    if which == "mimo":
        model = HybridWindowMoEKVModel(
            19072, 16384, (0, 1), (0, 1), 4096, 64, 4, 8, 192, 128, 64, 128,
            16384, 2048, 256, (0, 16), 8, rope_theta=1e7,
            window_rope_theta=1e4, value_scale=0.707, dtype=bfloat16)
        pool = (1, 1, 257, 64)
        return model, ((_bf16(pool + (768,), one_chip),
                        _bf16(pool + (512,), one_chip)),
                       (_bf16((1, 65, 128, 1536), one_chip),
                        _bf16((1, 65, 128, 1024), one_chip))), True
    model = _latent_model(2)
    return model, _bf16((2, 513, 64, model.pool_width), one_chip), False


#: digests of the programs (`_digest`): GPT-2's as the parent of the K/V
#: pair pool's two widths lowered them, LFM2's and Kimi's as they are since
#: their expert layers run as the kernel `expert_ffn`, MiMo's as they were
#: before the delta-rule model came beside it
_TODAY = {
    ("gpt2", "decode", 8): "efc3ab88f2af9e8a",
    ("gpt2", "prefill", 512): "4e3bc85e130ec77e",
    ("lfm2", "decode", 8): "084c7b792ff7e0a0",
    ("lfm2", "prefill", 512): "edb25a7e4c3f7c0b",
    ("kimi", "decode", 8): "76270e14e208675d",
    ("kimi", "prefill", 512): "4345b5dda78e1134",
    ("mimo", "decode", 8): "9134c9c04ff9a91e",
    ("mimo", "prefill", 512): "68604b4472d61158",
}


@pytest.mark.parametrize("which,what,n", sorted(_TODAY))
def test_the_other_models_lowered_programs_are_unchanged(
        on_tpu, one_chip, monkeypatch, which, what, n):
    """GPT-2's, LFM2's, Kimi's and MiMo's decode and prefill programs,
    lowered for the chip at their cells' widths, are the ones they were
    before the decode kernel took K and V at two widths, the chunk path a
    pair, and `rope` a rotary width (GPT-2's), since the expert layers
    became one kernel each (LFM2's and Kimi's), and before the delta-rule
    model's state came beside the pool (MiMo's): kernels and all."""
    from mxnet_tpu.ops.pallas_kernels import paged_attention

    monkeypatch.setattr(paged_attention, "_INTERPRET", False)
    model, cache, slots = _todays_model(one_chip, which)
    got = _digest(_lowered(one_chip, model, cache, what, n, slots))
    assert got == _TODAY[(which, what, n)], got


# -- the delta-rule hybrid at the served widths --------------------------------


def _delta_program(one_chip, what, n, n_blocks=257, slots=129):
    """`serve_decode_b<n>` or `serve_prefill_s<n>` of the OLMo hybrid block
    at its published widths, one linear layer and one full layer, tables of
    5,120 positions, compiled for the described chip with (pool, state)
    donated, with more slots than rows so that a copy of the state stands
    out from the rows' own values: (its text, its memory analysis, the
    model and the state's shapes)."""
    from mxnet_tpu.base import bfloat16
    from mxnet_tpu.serving import HybridDeltaKVModel

    model = HybridDeltaKVModel(100352, 5120, ["linear_attention",
                                              "full_attention"],
                               3840, 30, 30, 30, 96, 192, 4, 11008,
                               dtype=bfloat16)
    params = {k: _bf16(v, one_chip) for k, v in model.param_shapes().items()}
    conv, ssm = (jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
                 for a in jax.eval_shape(lambda: model.init_state(slots)))
    cache = (_bf16((1, 2, n_blocks, 64, 3840), one_chip), (conv, ssm))

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    if what == "decode":
        def prog(params, cache, token, pos, tables, slots):
            logits, cache = model.decode_paged(params, cache, token, pos,
                                               tables, slots=slots)
            return jnp.argmax(logits, axis=-1), cache

        args = (ints(n), ints(n), ints(n, 80), ints(n))
    else:
        def prog(params, cache, tokens, start, length, tables, slots):
            logits, cache = model.prefill_paged(params, cache, tokens, start,
                                                length, tables, slots=slots)
            return jnp.argmax(logits, axis=-1), cache

        args = (ints(1, n), ints(1), ints(1), ints(1, 80), ints(1))
    prog.__name__ = "serve_%s_%s%d" % (what, "b" if what == "decode" else "s",
                                       n)
    comp = jax.jit(prog, donate_argnums=(1,)).lower(params, cache,
                                                    *args).compile()
    return comp.as_text(), comp.memory_analysis(), (model, conv, ssm)


@pytest.mark.parametrize("batch", [8, 64])
def test_the_delta_decode_updates_its_state_in_place(on_tpu, one_chip,
                                                     monkeypatch, batch):
    """A decode launch at Olmo-Hybrid's widths: the full layer attends with
    one `paged_decode_attn` call at 30 heads of 128, the linear layer steps
    its rows' slots with one `delta_decode_step` call; pool, conv tails and
    the float32 state donated and aliased to the outputs; no value of the
    program copies the state (a gather and scatter of its slots as slices,
    or of its rows at 192 declared lanes, made the compiler copy the whole
    of it) and the temporaries stay those of the batch's rows; the names
    the `.olmo` metrics read are there."""
    from mxnet_tpu.ops.pallas_kernels import paged_attention

    monkeypatch.setattr(paged_attention, "_INTERPRET", False)
    program = "serve_decode_b%d" % batch
    text, memory, (model, conv, ssm) = _delta_program(one_chip, "decode",
                                                      batch)
    calls = [n for n in _op_names(text, program)
             if n.endswith("/pallas_call")]
    assert set(calls) == {"decode_attention/jit(_paged_decode)/"
                          "paged_decode_attn/pallas_call",
                          "delta_rule/jit(_step)/delta_decode_step/"
                          "pallas_call"}
    assert text.count("tpu_custom_call") == 2
    assert "input_output_alias" in text.splitlines()[0]
    pool_bytes = 2 * 257 * 64 * 3840 * 2
    state_bytes = ssm.size * 4 + conv.size * 2
    assert model.state_lanes == 256
    assert state_bytes == 129 * model.state_slot_bytes()
    # the conv tails' rows padded to whole tiles
    assert 0 <= memory.alias_size_in_bytes - pool_bytes - state_bytes \
        < conv.size * 2 // 16
    # a copy of the state, whole or a layer's, would be a temporary of
    # hundreds of MB
    assert memory.temp_size_in_bytes < 64 * 1024 * 1024 < ssm.size
    scopes = {part for n in _op_names(text, program)
              for part in n.split("/")}
    assert {"delta_rule", "linear_state", "short_conv", "qkv_proj",
            "qk_norm", "kv_scatter", "attn_out", "ffn", "lm_head"} <= scopes


def test_the_delta_prefill_walks_the_table_and_copies_no_state(on_tpu,
                                                              one_chip):
    """A chunk of 512 at Olmo-Hybrid's widths: K and V of a 5,120-position
    table at 3,840 lanes (78.6 MB) exceed `paged_prefill_attn`'s VMEM, so
    the full layer walks the table a step of 1,024 positions at a time
    (`paged_chunk_stream`) and no value holds float32 scores of the chunk
    against the table (what the table-wide gather would make); the chunked
    rule runs as a loop under `delta_rule`; no value copies the state."""
    program = "serve_prefill_s512"
    text, memory, (model, conv, ssm) = _delta_program(one_chip, "prefill",
                                                      512)
    assert "input_output_alias" in text.splitlines()[0]
    assert not _table_wide_scores(text, 512, 5120)
    names = _op_names(text, program)
    assert not any("kv_gather" in n or "pallas_call" in n for n in names)
    assert any(n.startswith("chunk_attention/while") for n in names)
    assert any(n.startswith("delta_rule/while") for n in names)
    # no copy of the state (380 MB here): the temporaries are the chunk's
    assert memory.temp_size_in_bytes < ssm.size * 4 // 3
    scopes = {part for n in names for part in n.split("/")}
    assert {"delta_rule", "linear_state", "short_conv", "chunk_attention",
            "kv_scatter", "ffn", "lm_head"} <= scopes
