"""`readers/paged_prefill_roofline.py`: a chunk's FLOPs and bytes against a
hand count for `gpt2-large` and `lfm2-8b-a1b-1chip`, that the count never
passes what a kernel over the row's live blocks has to do, and the reader on
made-up runs of the kernel and of the `jax.numpy` body."""
import pytest

from benchmark import spec
from benchmark.readers import paged_prefill_roofline as reader

GPT2 = spec.config("gpt2-large")
LFM2 = spec.config("lfm2-8b-a1b-1chip")
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_s": 819e9}
ARGS = {"replica": "bench", "programs": ["serve_prefill_"],
        "scopes": ["chunk_attention", "kv_gather"]}


class _Run:
    """What the reader touches of a `harness.Run`."""

    def __init__(self, cfg, records, raw, t_trace=10.0):
        self.cfg, self.peaks = cfg, PEAKS
        self.t_open, self.t_close, self._t_trace = 0.0, 14.0, t_trace
        self._spans = {"bench": records}
        self._raw = raw
        self.notes = []

    def note(self, msg):
        self.notes.append(msg)


def _chunk(t0, start, tokens):
    return {"type": "span", "phase": "prefill_chunk", "t0": t0,
            "t1": t0 + 0.002, "ms": 2.0,
            "attrs": {"start": start, "tokens": tokens, "attn_kernel": 1}}


def _op(start_ms, dur_ms, tf_op):
    return (int(start_ms * 1e6), int(dur_ms * 1e6),
            {"tf_op": tf_op, "display_name": "fusion"})


def test_a_gpt2_chunk_by_hand():
    assert reader.shape(GPT2) == (20, 20, 64, 36)
    # 512 tokens at position 0: their contexts sum to 512 x 513 / 2; QK^T
    # and PV over 64, 20 heads, 36 layers, 2 FLOPs a multiply-add
    fl, by = reader.chunk_work(GPT2, 0, 512)
    assert fl == 4 * 20 * 64 * 36 * 131328 == 24206376960
    # a layer: 512 rows of K and V, 1,280 wide, read once; 512 queries in
    # and outputs out, 1,280 wide; 2 bytes each
    assert by == 36 * (512 * 2 * 1280 + 2 * 512 * 1280) * 2 == 188743680
    # the bytes bound it: 0.230 ms against 0.123
    assert fl / 197e12 == pytest.approx(0.12288e-3, rel=1e-3)
    assert by / 819e9 == pytest.approx(0.23045e-3, rel=1e-3)
    # a prompt's second chunk: 256 tokens after 512
    fl, by = reader.chunk_work(GPT2, 512, 256)
    assert fl == 4 * 20 * 64 * 36 * (256 * 512 + 256 * 257 // 2)
    assert by == 36 * (768 * 2 * 1280 + 2 * 256 * 1280) * 2


def test_an_lfm2_chunk_by_hand():
    # 32 query heads of 64 over 8 K/V heads, the 4 attention layers of the
    # cut's 16
    assert reader.shape(LFM2) == (32, 8, 64, 4)
    fl, by = reader.chunk_work(LFM2, 1024, 512)
    assert fl == 4 * 32 * 64 * 4 * (512 * 1024 + 131328) == 21483225088
    # K and V of 1,536 rows at 512 wide; queries and outputs at 2,048
    assert by == 4 * (1536 * 2 * 512 + 2 * 512 * 2048) * 2 == 29360128
    # the products bound it
    assert fl / 197e12 > by / 819e9


@pytest.mark.parametrize("cfg,block,buckets,table", [
    (GPT2, 16, (16, 32, 64, 128, 256, 512), 1024),
    (LFM2, 64, (64, 128, 256, 512), 4608)], ids=["gpt2-large", "lfm2"])
def test_the_count_never_passes_what_a_kernel_must_do(cfg, block, buckets,
                                                      table):
    """No reading over 100 %: for every chunk the cells can run (a bucket's
    real tokens at a block-aligned start), the counted FLOPs and bytes are
    at most those of a kernel that walks only the row's live blocks (every
    query of the bucket against every position up to the chunk's last, whole
    blocks) and reads each once."""
    heads, kv_heads, hd, layers = reader.shape(cfg)
    for c in buckets:
        for start in range(0, table - c + 1, max(block, c // 2)):
            for tokens in {1, c // 2 + 1, c}:
                fl, by = reader.chunk_work(cfg, start, tokens)
                walked = -(-(start + c) // block) * block
                assert fl <= 4.0 * heads * hd * layers * c * walked
                assert by <= layers * 2 * (walked * 2 * kv_heads * hd
                                           + 2 * c * heads * hd)


@pytest.mark.parametrize("path", ["kernel", "jnp"])
def test_reads_the_traced_chunks_against_the_scopes_time(path):
    least = 0.23045e-3            # the bytes of a 512-token chunk at 0
    p = "jit(serve_prefill_s512)/"
    ops, modules = [], []
    for at in (11000.0, 12000.0):
        if path == "kernel":
            ops += [_op(at, 4e3 * least, p + "chunk_attention/jit(_paged_"
                        "prefill)/paged_prefill_attn/pallas_call:")]
        else:
            # the parent's: the gather and the float32 attention, 4 x the
            # least time together
            ops += [_op(at, 1e3 * least, p + "kv_gather/gather:"),
                    _op(at + 5, 3e3 * least, p + "chunk_attention/"
                        "dot_general:")]
        ops += [_op(at + 20, 1.0, p + "ffn/dot_general:")]
        modules.append((int(at * 1e6), int(10e6),
                        "jit_serve_prefill_s512(1)"))
    records = [_chunk(5.0, 9999, 512),             # before the trace
               _chunk(11.0, 0, 512), _chunk(12.0, 0, 512)]
    raw = {"ops": ops, "modules": modules,
           "spans": [(0, 1, "sched.launch", "main")]}
    run = _Run(GPT2, records, raw)
    assert reader.read(run, **ARGS) == pytest.approx(25.0, rel=1e-3)
    assert any("bound by bytes" in n and "512 2 x" in n for n in run.notes)


def test_nothing_to_read_is_none_and_a_lost_name_is_an_error():
    chunks = [_chunk(11.0, 0, 512)]
    # no device plane (the CPU rehearsal), or no store (an untraced run)
    assert reader.read(_Run(LFM2, chunks, None), **ARGS) is None
    assert reader.read(_Run(LFM2, chunks, {"ops": [], "modules": [],
                                           "spans": []}, t_trace=None),
                       **ARGS) is None
    # a program from before the names: nothing, and a note
    other = {"ops": [_op(11000.0, 1.0, "jit(f)/mul:")],
             "modules": [(int(11000e6), int(2e6), "jit_f(1)")], "spans": []}
    run = _Run(LFM2, chunks, other)
    assert reader.read(run, **ARGS) is None and run.notes
    # the program's other names and not these scopes: a rename must not
    # silence the metric
    named = dict(other, spans=[(0, 1, "sched.launch", "main")])
    with pytest.raises(ValueError, match="paged_prefill_roofline"):
        reader.read(_Run(LFM2, chunks, named), **ARGS)
