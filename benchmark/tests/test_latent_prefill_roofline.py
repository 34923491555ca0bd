"""`readers/latent_prefill_roofline.py`: a chunk's FLOPs and bytes against a
hand count for `kimi-k2.5-ep32`, and the reader on made-up runs."""
import pytest

from benchmark import spec
from benchmark.readers import latent_prefill_roofline as reader

CFG = spec.config("kimi-k2.5-ep32")
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_s": 819e9}
ARGS = {"replica": "bench", "programs": ["serve_prefill_"],
        "scopes": ["mla_prefill_attention"]}


class _Run:
    """What the reader touches of a `harness.Run`."""

    def __init__(self, records, raw, t_trace=10.0):
        self.cfg, self.peaks = CFG, PEAKS
        self.t_open, self.t_close, self._t_trace = 0.0, 14.0, t_trace
        self._spans = {"bench": records}
        self._raw = raw
        self.notes = []

    def note(self, msg):
        self.notes.append(msg)


def _chunk(t0, start, tokens):
    return {"type": "span", "phase": "prefill_chunk", "t0": t0,
            "t1": t0 + 0.002, "ms": 2.0,
            "attrs": {"start": start, "tokens": tokens}}


def _op(start_ms, dur_ms, tf_op):
    return (int(start_ms * 1e6), int(dur_ms * 1e6),
            {"tf_op": tf_op, "display_name": "fusion"})


def test_a_chunks_work_by_hand():
    # 512 tokens after a prefix of 1,024: their contexts sum to
    # 512 x 1,024 + 512 x 513 / 2; QK^T over 192 and PV over 128, 64 heads,
    # 8 layers, 2 FLOPs a multiply-add
    fl, by = reader.chunk_work(CFG, 1024, 512)
    assert fl == 2 * 8 * 64 * 320 * (524288 + 131328) == 214832250880
    # a layer: 1,536 latent rows of 576 values read once; 512 x 64 heads'
    # queries (192) in and values (128) out, 2 bytes each
    assert by == 8 * (1536 * 1152 + 512 * 64 * 320 * 2) == 181927936
    # the products bound it: 1.09 ms against 0.22
    assert fl / 197e12 == pytest.approx(1.0905e-3, rel=1e-3)
    assert by / 819e9 == pytest.approx(0.2221e-3, rel=1e-3)


def test_reads_the_traced_chunks_against_the_scopes_time():
    least = 214832250880 / 197e12
    p = "jit(serve_prefill_s512)/"
    ops, modules = [], []
    for at in (11000.0, 12000.0):
        # a kernel and an operation beside it under the scope: 4 x the
        # least time together; the loop's own event is not under it
        ops += [_op(at, 3e3 * least, p + "mla_prefill_attention/jit(_latent_"
                    "prefill)/latent_prefill_attn/pallas_call:"),
                _op(at + 10, 1e3 * least, p + "mla_prefill_attention/mul:"),
                _op(at + 20, 7.0, p + "mla_prefill_loop/while:"),
                _op(at + 30, 1.0, p + "lm_head/dot_general:")]
        modules.append((int(at * 1e6), int(60e6), "jit_serve_prefill_s512(1)"))
    records = [_chunk(5.0, 9999, 512),             # before the trace
               _chunk(11.0, 1024, 512), _chunk(12.0, 1024, 512),
               {"type": "span", "phase": "iteration", "t0": 11.5,
                "t1": 11.6, "ms": 100.0, "attrs": {"rows": 2}}]
    raw = {"ops": ops, "modules": modules,
           "spans": [(0, 1, "sched.launch", "main")]}
    run = _Run(records, raw)
    assert reader.read(run, **ARGS) == pytest.approx(25.0)
    assert any("bound by flops" in n for n in run.notes)
    assert any("by bucket: 512 2 x" in n for n in run.notes)
    assert any("by prefix" in n and "1 2 x" in n for n in run.notes)


def test_nothing_to_read_is_none_and_a_lost_name_is_an_error():
    chunks = [_chunk(11.0, 1024, 512)]
    # no device plane (the CPU rehearsal), or no store (an untraced run)
    assert reader.read(_Run(chunks, None), **ARGS) is None
    assert reader.read(_Run(chunks, {"ops": [], "modules": [], "spans": []},
                            t_trace=None), **ARGS) is None
    # a program from before the names: nothing, and a note
    other = {"ops": [_op(11000.0, 1.0, "jit(f)/mul:")],
             "modules": [(int(11000e6), int(2e6), "jit_f(1)")], "spans": []}
    run = _Run(chunks, other)
    assert reader.read(run, **ARGS) is None and run.notes
    # the program's other names and not this scope: a rename must not
    # silence the metric
    named = dict(other, spans=[(0, 1, "sched.launch", "main")])
    with pytest.raises(ValueError, match="latent_prefill_roofline"):
        reader.read(_Run(chunks, named), **ARGS)
