"""Device Context for mxnet_tpu.

Reference: `include/mxnet/base.h:90-175` (`Context{dev_type, dev_id}`) and
`python/mxnet/context.py` (current-context stack + `with` scope).

TPU-first design: a Context names a *logical* device `(dev_type, dev_id)` and
resolves lazily to a `jax.Device`.  `mx.tpu(i)` is the accelerator context (the
reference's `mx.gpu(i)` maps here — `gpu` is kept as an alias so reference
scripts run unchanged).  FOR TESTS ONLY, a `tpu` context resolves onto the
default backend's device list whatever its platform, so multi-device code
paths run on a forced multi-device CPU host the way the reference's tests map
`ctx_group`s onto cpu(0)/cpu(1)
(`tests/python/unittest/test_model_parallel.py:13-31`).  Nothing here says
which platform that was: code that needs the chip (`chip_smoke.py`,
`bench.py`) checks `ctx.jax_device().platform` itself and fails otherwise.
"""
from __future__ import annotations

import threading

from .base import MXNetError


class Context:
    """A logical device.  Value-semantic and hashable."""

    devtype2str = {1: "cpu", 2: "tpu", 3: "cpu_pinned"}
    devstr2type = {"cpu": 1, "tpu": 2, "gpu": 2, "cpu_pinned": 3}

    _default_ctx = threading.local()

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            if device_type not in Context.devstr2type:
                raise MXNetError("unknown device type %r" % (device_type,))
            self.device_typeid = Context.devstr2type[device_type]
            self.device_id = int(device_id)

    @property
    def device_type(self):
        return Context.devtype2str[self.device_typeid]

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_typeid == other.device_typeid
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __repr__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    def __str__(self):
        return self.__repr__()

    # -- jax resolution ---------------------------------------------------
    def jax_device(self):
        """Resolve to a concrete `jax.Device`.

        tpu -> devices of the DEFAULT backend; cpu -> cpu backend.  The
        default backend is the TPU wherever one is attached; without one
        (the CPU test mesh) `mx.tpu(i)` is CPU device i.  That resolution
        exists for tests: callers that must run on the chip check the
        returned device's ``platform``.
        In a multi-process job, contexts address THIS process's devices
        (copying a host value onto another process's device is impossible —
        global placement happens through shardings, not contexts).
        """
        import jax

        def _devs(platform=None):
            if jax.process_count() > 1:
                return jax.local_devices(backend=platform)
            return jax.devices(platform)

        if self.device_type in ("tpu", "gpu"):
            devs = _devs()  # default backend = accelerator when present
        else:
            try:
                devs = _devs("cpu")
            except RuntimeError:
                devs = _devs()
        if self.device_id >= len(devs):
            raise MXNetError(
                "context %s out of range: only %d %s device(s) visible"
                % (self, len(devs), devs[0].platform)
            )
        return devs[self.device_id]

    def __enter__(self):
        if not hasattr(Context._default_ctx, "stack"):
            Context._default_ctx.stack = []
        Context._default_ctx.stack.append(self)
        return self

    def __exit__(self, *args):
        Context._default_ctx.stack.pop()

    @staticmethod
    def default_ctx():
        stack = getattr(Context._default_ctx, "stack", None)
        if stack:
            return stack[-1]
        return Context("cpu", 0)


def cpu(device_id=0):
    """Return a CPU context."""
    return Context("cpu", device_id)


def tpu(device_id=0):
    """Return a TPU context (the reference's `mx.gpu`)."""
    return Context("tpu", device_id)


def gpu(device_id=0):
    """Alias of :func:`tpu` for reference-script compatibility."""
    return Context("tpu", device_id)


def current_context():
    """The context at the top of the `with mx.Context(...)` stack."""
    return Context.default_ctx()


def num_devices(device_type="tpu"):
    """Number of visible devices of a type (reference had no equivalent;
    used by DP helpers)."""
    import jax

    if device_type in ("tpu", "gpu"):
        return len(jax.devices())
    try:
        return len(jax.devices("cpu"))
    except RuntimeError:
        return len(jax.devices())
