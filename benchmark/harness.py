"""One run of one cell: the part every kind of cell shares.

`Run` carries what the cell's driver fills in (counts, end-to-end values, the
numbers compared) and what the per-layer readers read (counters, the reduced
trace).  `execute()` is the whole run but the look for a chip, so that the
tests can drive it on a CPU with the timed path broken underneath.
"""
from __future__ import annotations

import importlib
import json
import sys
import time

from . import chip, spec, trace


class Run:
    def __init__(self, cell, cfg, seed, seconds, traced, devices, peaks,
                 t_process, tiny=False):
        self.cell, self.cfg = cell, cfg
        self.seed, self.seconds = int(seed), float(seconds)
        self.traced, self.tiny = bool(traced), tiny
        self.devices, self.peaks = devices, peaks
        self.t_process = t_process
        self.compiles = chip.CompileCounter()
        self.e2e, self.counters, self.checks = {}, {}, {}
        self.attempted = self.failed = 0
        self.memory_peak = 0
        self.reduced = None          # trace.reduce() of the traced window
        self.t_open = self.t_close = None
        self._tracing = False
        self._trace_path = None
        self._trace_window = None

    def note(self, msg):
        """A line on standard error, stamped with the seconds since the
        process started: where set-up's time goes is read from these."""
        print("[bench +%.1fs] %s" % (time.perf_counter() - self.t_process,
                                     msg), file=sys.stderr, flush=True)

    # -- the window --------------------------------------------------------
    @property
    def window_seconds(self):
        """How long the window lasts.  A traced run's ends with its trace:
        writing a trace out stalls the host for seconds, and no work may be
        measured across that.  Its per-layer metrics are of that shorter
        window; end-to-end metrics come from untraced runs."""
        if not self.traced:
            return self.seconds
        return min(self.seconds, self.cell.get("trace_start_s", 0)
                   + self.cell["trace_seconds"])

    def open_window(self):
        """Everything before this instant is set-up."""
        if self.traced:
            self._trace_path = trace.trace_dir(chip.ROOT)
        self._compiles_at_open = self.compiles.n
        self.t_open = time.perf_counter()
        self.e2e["setup_s"] = self.t_open - self.t_process
        self.deadline = self.t_open + self.window_seconds
        self.tick()

    def tick(self):
        """Start the trace once the window has reached `trace_start_s`.
        The cell's loop calls this as it goes round."""
        import jax

        if not self.traced or self._tracing or self._trace_window:
            return
        if time.perf_counter() - self.t_open \
                < self.cell.get("trace_start_s", 0):
            return
        jax.profiler.start_trace(self._trace_path)
        self._tracing = True
        self._t_trace = time.perf_counter()

    def close_window(self, sync=None, at_close=None):
        """``sync`` waits for the device work the window started;
        ``at_close`` reads what has to be read at the closing instant,
        before the trace is written out (which takes seconds)."""
        import jax

        if sync is not None:
            sync()
        self.t_close = time.perf_counter()
        self.window_s = self.t_close - self.t_open
        if at_close is not None:
            at_close()
        if self._tracing:
            self._trace_window = self.t_close - self._t_trace
            jax.profiler.stop_trace()
            self._tracing = False
        self.checks["compiles_in_window"] = {
            "value": self.compiles.n - self._compiles_at_open, "limit": 0}

    def reduce_trace(self):
        if not self.traced:
            return
        path = trace.find_xplane(self._trace_path)
        if path is None:
            self.note("the profiler wrote no .xplane.pb")
            return
        t0 = time.perf_counter()
        self.reduced = trace.reduce(path, window_s=self._trace_window)
        self.note("trace %s reduced in %.1fs"
                  % (path, time.perf_counter() - t0))


def per_layer(run):
    """Every per-layer metric this cell lists, through its own reader.  A
    reader that finds nothing to read returns None and the metric is left
    out of the line."""
    out = {}
    for m in spec.metrics_for(run.cell["name"], "per_layer"):
        read, args = spec.reader(m["name"])
        value = read(run, **args)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def end_to_end(run):
    out = {}
    for m in spec.metrics_for(run.cell["name"], "end_to_end"):
        if m["name"] in run.e2e:
            out[m["name"]] = {"value": float(run.e2e[m["name"]]),
                              "unit": m["unit"]}
    return out


def execute(workload, seed, seconds, traced, devices, t_process,
            tiny=False):
    """The whole run but the look for a chip.  Returns the result line as a
    dict."""
    cell = spec.workload(workload)
    cfg = spec.config(cell["config"], tiny=tiny)
    if tiny:
        cell.update(cell.get("tiny", {}))
    run = Run(cell, cfg, seed, seconds, traced, devices,
              chip.peaks(devices[0], tiny=tiny), t_process, tiny=tiny)
    driver = importlib.import_module("benchmark." + cell["kind"])
    driver.run(run)
    run.reduce_trace()

    device = chip.device_info(devices)
    device["memory_peak_bytes"] = int(run.memory_peak)
    result = {"correct": None, "attempted": int(run.attempted),
              "failed": int(run.failed)}
    if traced:
        result["metrics"] = per_layer(run)
        if run.reduced is not None:
            device["busy_s"] = run.reduced["busy_s"]
            device["window_s"] = run.reduced["window_s"]
            result["breakdown"] = run.reduced["breakdown"]
    else:
        result["metrics"] = end_to_end(run)
    result["device"] = device
    result["correct"] = bool(run.checks) and all(
        holds(c) for c in run.checks.values())
    # the numbers compared come last, on standard error and in the line
    result["checks"] = run.checks
    return result


def holds(check):
    """A number compared holds if it is at most its limit, or, where the
    check says ``at_least``, at least its limit.  NaN never holds."""
    if check.get("at_least"):
        return check["value"] >= check["limit"]
    return check["value"] <= check["limit"]


def print_result(result):
    for name, c in result["checks"].items():
        extra = {k: v for k, v in c.items()
                 if k not in ("value", "limit", "at_least")}
        print("[check] %s = %.6g (limit %s%.6g)%s %s"
              % (name, c["value"], "at least " if c.get("at_least") else "",
                 c["limit"], "" if holds(c) else "  <-- FAILS",
                 json.dumps(extra) if extra else ""),
              file=sys.stderr, flush=True)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
