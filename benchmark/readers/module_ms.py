"""The median device time, in milliseconds, of one launch of the jitted
programs whose names start with one of ``programs``: the "XLA Modules"
events `jit_<program>...(...)` of the traced window.  The program names its
serving programs (`serve_decode_b32`, `serve_prefill_s512`), so a decode
launch is told from a prefill chunk on the device's own clock.

Nothing to read (None): no device plane, or a program that writes no names
(`xplane_raw.named`).  A program that does, with no module of these names in
the window, is an error: a renamed program must not fall silent."""
import statistics

from benchmark import xplane_raw


def read(run, programs):
    raw = xplane_raw.of_run(run)
    if raw is None:
        return None
    ms = [d / 1e6 for _, d, _ in xplane_raw.programs_of(raw, programs)]
    if not ms:
        what = "none of the %d module events is named %s" \
            % (len(raw["modules"]), programs)
        if xplane_raw.named(raw):
            raise ValueError("module_ms: %s, in a trace that holds the "
                             "program's other names" % what)
        run.note("module_ms: %s (a program that writes no names)" % what)
        return None
    run.note("modules %s: %d launches, %.4fs on the device, median %.3f ms"
             % (programs, len(ms), sum(ms) / 1e3, statistics.median(ms)))
    return statistics.median(ms)
