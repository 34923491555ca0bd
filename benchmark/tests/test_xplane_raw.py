"""The raw `XSpace` decoder against the recorded traces, and each reader of
the program's own names on event lists built by hand.

`data/small.xplane.pb` (`record_trace.py`): four calls of a jitted `loss` with
the flash kernels.  `data/serve_small.xplane.pb` (`record_serve_trace.py`): a
tiny engine serving five requests, with `sched.*` spans, `jit_serve_*`
modules and scoped operations; the numbers it is held to here were worked
out from the file by hand (`record_serve_trace.py` prints them)."""
import os
from types import SimpleNamespace

import pytest

from benchmark import trace, xplane_raw
from benchmark.readers import (idle_in_spans, module_ms, scope_ms,
                               span_attr_peak, span_ms, span_percentile)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SMALL = os.path.join(DATA, "small.xplane.pb")
SERVE = os.path.join(DATA, "serve_small.xplane.pb")


def _run(raw, **kw):
    """What a reader sees of a `harness.Run`."""
    notes = []
    return SimpleNamespace(_raw=raw, note=notes.append, notes=notes, **kw)


# -- the decoder --------------------------------------------------------------


def test_metadata_of_a_kernel_event():
    raw = xplane_raw.load(SMALL)
    fwd = [m for _, _, m in raw["ops"] if m["display_name"] == "jvp__.1"]
    assert len(fwd) == 4
    assert fwd[0]["tf_op"] == "jit(loss)/jvp()/pallas_call:"
    assert fwd[0]["hlo_category"] == "custom-call"
    assert fwd[0]["name"].startswith("%jvp__.1 = (bf16[2,4,512,64]")
    assert fwd[0]["source"].endswith("flash_attention.py:177")
    assert int(fwd[0]["flops"]) == 536870912


def test_modules_line():
    raw = xplane_raw.load(SMALL)
    assert [n.split("(")[0] for _, _, n in raw["modules"]] == ["jit_loss"] * 4
    assert [d for _, d, _ in raw["modules"]] == [56687, 56691, 56651, 56728]
    assert len(xplane_raw.programs_of(raw, ["loss"])) == 4
    assert xplane_raw.programs_of(raw, ["los_", "step"]) == []
    assert raw["spans"] == []       # the program wrote no span of its own


def test_agrees_with_profile_data_on_every_start_and_duration():
    loaded = trace.load_events(SMALL)
    mine = {p["name"]: p["lines"] for p in xplane_raw.planes(SMALL)}
    ops = mine["/device:TPU:0"]["XLA Ops"]
    assert [(s, d) for s, d, _ in ops] \
        == [(s, d) for s, d, _, _ in loaded["devices"][0]]
    # the event's name is the text `ProfileData` shows
    assert [m["name"] for _, _, m in ops] \
        == [text for _, _, _, text in loaded["devices"][0]]
    host = sorted((s, d, "%s/%s" % (line, m["name"]))
                  for line, events in mine["/host:CPU"].items()
                  for s, d, m in events if d > 0)
    assert host == sorted(loaded["host"])
    assert len(host) > 100


def test_host_filter_keeps_only_the_named_events():
    kept = {p["name"]: p["lines"]
            for p in xplane_raw.planes(SMALL, host_prefixes=("$time",))}
    names = {m["name"] for events in kept["/host:CPU"].values()
             for _, _, m in events}
    assert names == {"$time perf_counter", "$time sleep"}
    assert len(kept["/device:TPU:0"]["XLA Ops"]) == 64   # devices untouched


def test_a_trace_without_a_device_plane_loads_as_none(tmp_path):
    # an XSpace of one host plane: field 1 (plane) { field 2 (name) }
    name = b"/host:CPU"
    plane = bytes([0x12, len(name)]) + name
    path = tmp_path / "cpu.xplane.pb"
    path.write_bytes(bytes([0x0A, len(plane)]) + plane)
    assert xplane_raw.load(str(path)) is None
    assert [p["name"] for p in xplane_raw.planes(str(path))] == ["/host:CPU"]


@pytest.mark.parametrize("tf_op,scopes", [
    ("jit(step)/transpose(jvp(pred))/fused_ce_bwd_dw/pallas_call:",
     ["step", "pred", "fused_ce_bwd_dw", "pallas_call"]),
    ("jit(serve_mega_b8)/while/body/kv_gather/gather:",
     ["serve_mega_b8", "while", "body", "kv_gather", "gather"]),
    ("jit(step)/jvp(layer3_ffn1)/dot_general:",
     ["step", "layer3_ffn1", "dot_general"]),
    ("jit(loss)/jvp()/pallas_call:", ["loss", "", "pallas_call"]),
    ("jit(step)/checkpoint(rematted_computation(jvp(layer0_attn)))/mul",
     ["step", "layer0_attn", "mul"]),
    (None, [""]),
])
def test_scopes_are_read_through_their_wrappers(tf_op, scopes):
    assert xplane_raw.scopes_of(tf_op) == scopes


# -- the readers, on event lists built by hand --------------------------------

US = 1000      # the hand-built lists are in microseconds; events in ns


def _op(start, dur, tf_op=None, display="fusion.1"):
    meta = {"name": "%" + display, "display_name": display}
    if tf_op:
        meta["tf_op"] = tf_op
    return (start * US, dur * US, meta)


def _hand_built():
    """Two decode launches and a prefill chunk.  Times in microseconds.

    modules: decode [1000, 1600), prefill [2000, 2900), decode [4000, 4700)
    device idle: [1600, 2000) and [2900, 4000): 1500 us in all."""
    d, p = "jit(serve_decode_b4)/", "jit(serve_prefill_s16)/"
    ops = [
        _op(1000, 100, d + "embed/gather:"),
        _op(1100, 300, d + "kv_gather/gather:", "slice_bitcast_fusion.3"),
        _op(1400, 200, d + "decode_attention/dot_general:", "fusion.7"),
        _op(2000, 500, p + "kv_gather/gather:", "slice_bitcast_fusion.9"),
        _op(2500, 400, p + "chunk_attention/dot_general:"),
        _op(4000, 100),                       # a copy with no name stack
        _op(4100, 250, d + "while/body/kv_gather/gather:",
            "slice_bitcast_fusion.3"),
        _op(4350, 350, d + "ffn/dot_general:"),
    ]
    modules = [(1000, 600, "jit_serve_decode_b4(11)"),
               (2000, 900, "jit_serve_prefill_s16(12)"),
               (4000, 700, "jit_serve_decode_b4(11)")]
    line = "python3"
    spans = [
        (900, 900, "sched.iteration", line),      # [900, 1800): launched
        (900, 50, "sched.pack", line),
        (950, 100, "sched.launch", line),         # [950, 1050)
        (1050, 650, "sched.fetch", line),         # [1050, 1700): idle 100
        (1700, 100, "sched.publish", line),       # [1700, 1800): idle 100
        (1800, 2050, "sched.iteration", line),    # [1800, 3850): no launch
        (1850, 1200, "sched.prefill", line),      # [1850, 3050): idle 300
        (3050, 700, "sched.admit", line),         # [3050, 3750): idle 700
        (3900, 900, "sched.iteration", line),     # [3900, 4800): launched
        (3900, 50, "sched.pack", line),           # idle 50
        (3950, 100, "sched.launch", line),        # [3950, 4050): idle 50
        (4050, 700, "sched.fetch", line),
    ]
    return {"ops": ops,
            "modules": [(s * US, d * US, n) for s, d, n in modules],
            "spans": sorted((s * US, d * US, n, ln)
                            for s, d, n, ln in spans)}


def test_overlap_of_gaps_and_spans():
    gaps = [(10, 20), (30, 40), (50, 90)]
    assert idle_in_spans.overlap(gaps, [(0, 100)]) == 60
    assert idle_in_spans.overlap(gaps, [(15, 35)]) == 10
    assert idle_in_spans.overlap(gaps, [(15, 35), (35, 60), (95, 99)]) == 25
    assert idle_in_spans.overlap(gaps, [(20, 30), (40, 50)]) == 0
    assert idle_in_spans.overlap([], [(0, 5)]) == 0


def test_idle_by_phase_and_per_iteration():
    by_phase, idle, iterations = idle_in_spans.table(_hand_built())
    assert idle == pytest.approx(1500e-6)
    assert iterations == 2           # the middle iteration launched nothing
    assert {k: round(v * 1e6) for k, v in by_phase.items()} == {
        "sched.pack": 50, "sched.launch": 50, "sched.fetch": 100,
        "sched.publish": 100, "sched.prefill": 300, "sched.admit": 700}
    run = _run(_hand_built())
    ms = 1e-3                        # a microsecond, in milliseconds
    assert idle_in_spans.read(run, spans=["sched.fetch"]) \
        == pytest.approx(100 * ms / 2)
    assert idle_in_spans.read(
        run, spans=["sched.sweep", "sched.prefill", "sched.admit",
                    "sched.grow"]) == pytest.approx(1000 * ms / 2)
    assert idle_in_spans.read(run, spans=["sched.pack", "sched.launch"]) \
        == pytest.approx(100 * ms / 2)
    # 1500 idle, 1300 inside a phase: 200 (the gap's ends at 1800-1850 and
    # 3750-3900) inside none
    assert idle_in_spans.read(run, outside=True) \
        == pytest.approx(100.0 * 200 / 1500)
    assert len(run.notes) == 1 and "by phase" in run.notes[0]


def _unnamed(raw):
    """The same trace as a program from before the names leaves it: no
    span, every program a `jit_prog`."""
    return dict(raw, spans=[], modules=[(s, d, "jit_prog(1)")
                                        for s, d, _ in raw["modules"]])


def test_a_program_that_writes_no_names_reads_nothing():
    raw = _unnamed(_hand_built())
    assert not xplane_raw.named(raw)
    run = _run(raw)
    assert idle_in_spans.read(run, spans=["sched.fetch"]) is None
    assert idle_in_spans.read(run, outside=True) is None
    assert module_ms.read(run, programs=["serve_decode_"]) is None
    assert scope_ms.read(run, programs=["serve_decode_"],
                         scopes=["kv_gather"]) is None
    assert scope_ms.read(run, programs=["prog"], scopes=["optimizer"]) is None
    assert scope_ms.read(run, programs=["serve_decode_"],
                         unscoped=True) is None
    assert span_ms.read(run, span="train_step", q=50) is None
    assert sum("writes no names" in n for n in run.notes) == 6


@pytest.mark.parametrize("read,args", [
    (idle_in_spans.read, {"spans": ["sched.fetch"]}),     # the spans are gone
    (idle_in_spans.read, {"outside": True}),
    (module_ms.read, {"programs": ["serve_verify_"]}),    # a program renamed
    (scope_ms.read, {"programs": ["serve_verify_"], "scopes": ["ffn"]}),
    (scope_ms.read, {"programs": ["serve_decode_"], "unscoped": True,
                     "_raw": "no_decode"}),
    (scope_ms.read, {"programs": ["serve_decode_"],
                     "scopes": ["optimizer"]}),           # a scope renamed
    (span_ms.read, {"span": "train_step", "q": 50}),
])
def test_a_missing_name_is_an_error_where_the_program_writes_names(read,
                                                                   args):
    raw = _hand_built()
    if read is idle_in_spans.read:
        raw["spans"] = []           # the `jit_serve_*` modules still say so
    if args.pop("_raw", None) == "no_decode":
        raw["modules"] = [m for m in raw["modules"] if "prefill" in m[2]]
    assert xplane_raw.named(raw)
    with pytest.raises(ValueError, match="program's other names"):
        read(_run(raw), **args)


def test_module_time_by_program_name():
    run = _run(_hand_built())
    assert module_ms.read(run, programs=["serve_decode_", "serve_mega_"]) \
        == pytest.approx((600 + 700) / 2 * 1e-3)
    assert module_ms.read(run, programs=["serve_prefill_"]) \
        == pytest.approx(900e-3)


def test_scope_time_per_launch_inside_the_named_programs():
    run = _run(_hand_built())
    decode = ["serve_decode_", "serve_mega_"]
    # kv_gather in the decode launches only (300, and 250 inside a scan),
    # not the prefill chunk's 500; decode_attention 200 in the first: the
    # two launches hold 500 and 250 us, and the median is between them
    assert scope_ms.read(run, programs=decode,
                         scopes=["kv_gather", "decode_attention"]) \
        == pytest.approx((500e-3 + 250e-3) / 2)
    assert scope_ms.read(run, programs=["serve_prefill_"],
                         scopes=["kv_gather"]) == pytest.approx(500e-3)
    # the copy with no name stack, in the second launch alone
    assert scope_ms.read(run, programs=decode, unscoped=True) \
        == pytest.approx((0 + 100e-3) / 2)
    assert scope_ms.read(run, programs=["serve_prefill_"],
                         unscoped=True) == 0.0
    raw = _hand_built()
    launches = scope_ms.launches_of(
        raw["ops"], xplane_raw.programs_of(raw, ["serve_decode_"]))
    assert [len(launch) for launch in launches] == [3, 3]
    ops = launches[0] + launches[1]
    table = {k: round(v * 1e6) for k, v in scope_ms.by_scope(ops).items()}
    assert table == {"embed": 100, "kv_gather": 300, "decode_attention": 200,
                     "(no scope)": 100, "while": 250, "ffn": 350}
    assert any("slice_bitcast_fusion 0.0005" in n for n in run.notes)


def test_backward_operations_count_under_their_node():
    s = "jit(step)/"
    ops = [_op(0, 100, s + "jvp(pred)/fused_ce_fwd/pallas_call:"),
           _op(100, 300, s + "transpose(jvp(pred))/fused_ce_bwd_dw/"
               "pallas_call:"),
           _op(400, 50, s + "transpose(jvp(layer11_ffn1))/dot_general:"),
           _op(450, 150, s + "optimizer/mul:"),
           _op(1000, 600, s + "optimizer/mul:")]
    raw = {"ops": ops, "spans": [],
           "modules": [(0, 600 * US, "jit_step(7)"),
                       (1000 * US, 600 * US, "jit_step(7)")]}
    run = _run(raw)
    assert scope_ms.read(run, programs=["step"], scopes=["pred"]) \
        == pytest.approx((400e-3 + 0) / 2)
    assert scope_ms.read(run, programs=["step"], scopes=["optimizer"]) \
        == pytest.approx((150e-3 + 600e-3) / 2)
    assert "layerN_ffnN 0.0001" in run.notes[0]      # digits folded


def test_a_launch_the_trace_cuts_short_does_not_move_the_median():
    d = "jit(serve_decode_b4)/"
    ops, modules = [], []
    for i in range(5):
        t = 1000 * i
        ops += [_op(t, 300, d + "kv_gather/gather:"), _op(t + 300, 100)]
        modules.append((t * US, 400 * US, "jit_serve_decode_b4(1)"))
    # the sixth launch: the trace ends 50 us into its gather
    ops.append(_op(5000, 50, d + "kv_gather/gather:"))
    modules.append((5000 * US, 50 * US, "jit_serve_decode_b4(1)"))
    run = _run({"ops": ops, "modules": modules, "spans": []})
    assert scope_ms.read(run, programs=["serve_decode_"],
                         scopes=["kv_gather"]) == pytest.approx(300e-3)
    assert scope_ms.read(run, programs=["serve_decode_"], unscoped=True) \
        == pytest.approx(100e-3)
    assert module_ms.read(run, programs=["serve_decode_"]) \
        == pytest.approx(400e-3)


def test_host_time_of_the_programs_own_spans():
    line = "python3"
    spans = [(i * 1000 * US, ms * US, "train_step", line)
             for i, ms in enumerate([500, 700, 900, 20000])]
    spans.append((0, 5 * US, "sched.sweep", line))
    run = _run({"ops": [], "modules": [], "spans": sorted(spans)})
    assert span_ms.read(run, span="train_step", q=50) \
        == pytest.approx(0.8)        # the one step that waited is a tail
    assert span_ms.read(run, span="train_step", q=100) == pytest.approx(20.0)
    assert "4, 0.0221s on the host" in run.notes[0]


def test_every_device_reader_reads_nothing_on_a_cpu_run():
    run = _run(None)
    assert module_ms.read(run, programs=["serve_decode_"]) is None
    assert scope_ms.read(run, programs=["step"], scopes=["pred"]) is None
    assert scope_ms.read(run, programs=["step"], unscoped=True) is None
    assert span_ms.read(run, span="train_step", q=50) is None
    assert idle_in_spans.read(run, spans=["sched.fetch"]) is None
    assert idle_in_spans.read(run, outside=True) is None
    # an untraced run has no trace directory at all
    bare = SimpleNamespace()
    assert xplane_raw.of_run(bare) is None and bare._raw is None


def test_span_store_readers():
    from mxnet_tpu import tracing

    tracing.reset()
    try:
        for i, ms in enumerate([10.0, 20.0, 30.0, 40.0]):
            tracing.add_span(i + 1, "queue_wait", "bench-t", 1.0,
                             1.0 + ms / 1e3)
        for live in (7, 12, 9):
            tracing.add_span(0, "iteration", "bench-t", 1.0, 1.02,
                             rows=2, blocks_live=live, blocks_parked=50)
        tracing.add_span(0, "iteration", "bench-t", 5.0, 6.0,
                         blocks_live=90)            # after the window
        run = _run(None, t_open=1.0, t_close=2.0, cell={"n_blocks": 48})
        assert span_percentile.read(run, "bench-t", "queue_wait", 50) \
            == pytest.approx(25.0)
        assert span_percentile.read(run, "bench-t", "queue_wait", 100) \
            == pytest.approx(40.0)
        # the store has a window and no span of the phase: an error
        with pytest.raises(ValueError, match="phase 'prefill'"):
            span_percentile.read(run, "bench-t", "prefill", 50)
        assert span_attr_peak.read(run, "bench-t", "iteration",
                                   "blocks_live", "n_blocks") \
            == pytest.approx(100.0 * 12 / 48)
        with pytest.raises(ValueError, match="phase 'iteration'"):
            span_attr_peak.read(run, "bench-t", "iteration", "no_such",
                                "n_blocks")
        with pytest.raises(ValueError, match="'nobody' holds 0 records"):
            span_percentile.read(_run(None, t_open=1.0, t_close=2.0),
                                 "nobody", "queue_wait", 50)
        # a run with no window, or a program whose store has no window()
        assert span_percentile.read(
            _run(None, t_open=None, t_close=None), "bench-t", "queue_wait",
            50) is None
        window = tracing.window
        del tracing.window
        try:
            assert span_percentile.read(
                _run(None, t_open=1.0, t_close=2.0), "bench-t",
                "queue_wait", 50) is None
            assert span_attr_peak.read(
                _run(None, t_open=1.0, t_close=2.0, cell={"n_blocks": 48}),
                "bench-t", "iteration", "blocks_live", "n_blocks") is None
        finally:
            tracing.window = window
    finally:
        tracing.reset()


# -- the readers, on the recorded serving trace -------------------------------
# Worked out from the file with `jax.profiler.ProfileData` alone and a
# nanosecond-by-nanosecond busy map (not with the code under test): the
# module durations, the idle nanoseconds inside each phase's spans, the count
# of operations inside the decode launches and their time.  The readers
# leave out gaps under a microsecond (`trace.busy_union`), 10,148 ns in all
# here, hence the tolerance.  Times by scope are `tf_op`'s, which only the raw
# file has: `record_serve_trace.py` printed them on the chip.

DECODE_NS = [23892, 23805, 24003, 23880, 23881]
PREFILL_NS = [26696, 26656, 26786]
IDLE_NS = {"sched.sweep": 40919, "sched.prefill": 17509,
           "sched.admit": 9921209, "sched.grow": 298341,
           "sched.pack": 8636919, "sched.launch": 2216740,
           "sched.fetch": 3613109, "sched.publish": 110050}
IDLE_TOTAL_NS = 25449367
ITERATIONS = 4         # five launches; the trace began inside the first's


@pytest.fixture(scope="module")
def serve_raw():
    return xplane_raw.load(SERVE)


def test_recorded_serving_trace_holds_the_programs_names(serve_raw):
    assert os.path.getsize(SERVE) <= 500 * 1024
    names = [n.split("(")[0] for _, _, n in serve_raw["modules"]]
    assert names == ["jit_serve_prefill_s32"] * 3 + ["jit_serve_decode_b4"] * 5
    assert not any(n.startswith("jit_prog") for n in names)
    spans = {}
    for _, _, name, line in serve_raw["spans"]:
        spans.setdefault(name, set()).add(line)
    assert set(spans) == {"sched." + p for p in (
        "iteration", "sweep", "prefill", "admit", "grow", "pack", "launch",
        "fetch", "publish")}
    # one thread wrote them all
    assert len(set.union(*spans.values())) == 1
    scopes = set()
    for _, _, meta in serve_raw["ops"]:
        scopes.update(xplane_raw.scopes_of(meta.get("tf_op"))[1:-1])
    assert scopes >= {"embed", "qkv_proj", "kv_scatter", "kv_gather",
                      "decode_attention", "chunk_attention", "attn_out",
                      "ffn", "lm_head", "sampler"}


def test_module_reader_on_the_recorded_trace(serve_raw):
    run = _run(serve_raw)
    assert module_ms.read(run, programs=["serve_decode_", "serve_mega_"]) \
        == pytest.approx(23881e-6)                 # the median of five
    assert module_ms.read(run, programs=["serve_prefill_"]) \
        == pytest.approx(26696e-6)
    assert [d for _, d, _ in xplane_raw.programs_of(
        serve_raw, ["serve_decode_"])] == DECODE_NS
    assert [d for _, d, _ in xplane_raw.programs_of(
        serve_raw, ["serve_prefill_"])] == PREFILL_NS


def test_idle_reader_on_the_recorded_trace(serve_raw):
    by_phase, idle, iterations = idle_in_spans.table(serve_raw)
    assert iterations == ITERATIONS
    assert idle == pytest.approx(IDLE_TOTAL_NS / 1e9, rel=1e-3)
    assert set(by_phase) == set(IDLE_NS)
    for name, ns in IDLE_NS.items():
        assert by_phase[name] == pytest.approx(ns / 1e9, rel=2e-3), name
    run = _run(serve_raw)
    assert idle_in_spans.read(run, spans=["sched.pack", "sched.launch"]) \
        == pytest.approx((8636919 + 2216740) / 1e6 / ITERATIONS, rel=2e-3)
    assert idle_in_spans.read(run, spans=["sched.fetch"]) \
        == pytest.approx(3613109 / 1e6 / ITERATIONS, rel=2e-3)
    # 24,854,796 of the 25,449,367 idle ns lie inside a phase
    assert idle_in_spans.read(run, outside=True) \
        == pytest.approx(100.0 * (1 - 24854796 / IDLE_TOTAL_NS), rel=0.02)


def test_scope_reader_on_the_recorded_trace(serve_raw):
    decode = xplane_raw.programs_of(serve_raw, ["serve_decode_"])
    launches = scope_ms.launches_of(serve_raw["ops"], decode)
    assert [len(launch) for launch in launches] == [140] * 5
    ops = [op for launch in launches for op in launch]
    assert sum(d for _, d, _ in ops) == 117252
    table = {k: round(v * 1e9) for k, v in scope_ms.by_scope(ops).items()}
    assert table["kv_gather"] == 4116 and table["decode_attention"] == 22057
    assert table["sampler"] == 30125 and table["(no scope)"] == 42893
    assert sum(table.values()) == 117252
    run = _run(serve_raw)
    # by launch: 5235, 5234, 5235, 5238, 5231 ns under the two scopes and
    # 8585, 8495, 8693, 8562, 8558 under none (26,173 and 42,893 in all)
    assert scope_ms.read(run, programs=["serve_decode_", "serve_mega_"],
                         scopes=["kv_gather", "decode_attention"]) \
        == pytest.approx(5235e-6)
    assert scope_ms.read(run, programs=["serve_decode_", "serve_mega_"],
                         unscoped=True) == pytest.approx(8562e-6)
    # the prefill chunks gather too, and attend under their own name
    assert scope_ms.read(run, programs=["serve_prefill_"],
                         scopes=["chunk_attention"]) > 0
    with pytest.raises(ValueError, match="no operation under"):
        scope_ms.read(run, programs=["serve_prefill_"],
                      scopes=["decode_attention"])
    assert xplane_raw.named(serve_raw)
    assert not xplane_raw.named(xplane_raw.load(SMALL))
