"""Persist measured benchmark results as JSON artifacts.

Every successful measurement writes one JSON file under `bench_results/`
(`bench.py` its headline record, `tools/benchmark_io.py` the input-pipeline
numbers that `bench.py` then surfaces beside its own).  Nothing replays an
artifact in place of a run: a benchmark that finds no chip fails.

Artifacts are plain JSON files named `<kind>_<utc-stamp>.json`, written
atomically (tmp + rename) so a crash mid-write can never leave a torn
newest artifact.  Each carries `measured_at`; `latest()` adds the file name
it came from as `replayed_from`, so a consumer can always tell a stored
number from a fresh one.
"""
from __future__ import annotations

import datetime
import json
import os
import tempfile

_HERE = os.path.dirname(os.path.abspath(__file__))
RESULTS_DIR = os.environ.get(
    "MXNET_BENCH_RESULTS_DIR", os.path.join(_HERE, "..", "bench_results"))


def _utcnow():
    return datetime.datetime.now(datetime.timezone.utc).strftime(
        "%Y%m%dT%H%M%SZ")


_seq = 0


def _file_stamp():
    """Filename stamp: microsecond UTC + pid + in-process counter, so
    writes in the same microsecond — within one process or across two
    concurrent ones — still get distinct, write-ordered names."""
    global _seq
    _seq += 1
    now = datetime.datetime.now(datetime.timezone.utc).strftime(
        "%Y%m%dT%H%M%S.%fZ")
    return "%s-%d-%06d" % (now, os.getpid(), _seq)


def record(result, kind="bench", results_dir=None):
    """Write ``result`` (a dict) as the newest ``kind`` artifact.

    Adds ``measured_at`` (UTC, ISO-ish stamp) unless the caller already
    set one (e.g. when transcribing a measurement taken earlier in the
    round).  Returns the artifact path.
    """
    results_dir = results_dir or RESULTS_DIR
    os.makedirs(results_dir, exist_ok=True)
    out = dict(result)
    out.setdefault("measured_at", _utcnow())
    # the filename stamp orders artifacts in write order even when
    # measured_at was supplied by the caller (see _file_stamp)
    fd, tmp = tempfile.mkstemp(dir=results_dir, suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(out, f, indent=1, sort_keys=True)
            f.write("\n")
        path = os.path.join(
            results_dir, "%s_%s.json" % (kind, _file_stamp()))
        os.rename(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)
    return path


def latest(kind="bench", results_dir=None):
    """Newest ``kind`` artifact as a dict, or None if none exist.

    Newest by filename stamp (write order), not by file mtime — a later
    checkout/copy must not reorder the history.  Unreadable/torn files are
    skipped (record() writes atomically, but a truncated disk is not a
    reason to crash the reader).
    """
    results_dir = results_dir or RESULTS_DIR
    if not os.path.isdir(results_dir):
        return None
    names = sorted(n for n in os.listdir(results_dir)
                   if n.startswith(kind + "_") and n.endswith(".json"))
    for name in reversed(names):
        try:
            with open(os.path.join(results_dir, name)) as f:
                out = json.load(f)
            out["replayed_from"] = name
            return out
        except (OSError, ValueError):
            continue
    return None
