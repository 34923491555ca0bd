"""Multi-host SPMD initialization.

The reference scaled across machines with the parameter server
(`tools/launch.py` + `DMLC_*` env).  SPMD jobs scale across hosts the
jax way instead: every host runs the same program, `jax.distributed`
connects them, and a Mesh laid over `jax.devices()` then spans all hosts —
the same `SPMDTrainer`/`shard_map` code runs unchanged, with XLA routing
collectives over ICI within a slice and DCN across slices.

`init_from_env()` keeps the launcher's env contract so one entry point
serves both worlds: it reads the `DMLC_*` variables `tools/launch.py`
already sets (or the standard JAX coordinator variables when present) and
brings up the process group.
"""
from __future__ import annotations

import logging
import os

import jax

from ..base import MXNetError

_initialized = False


def init_from_env(coordinator=None, num_processes=None, process_id=None):
    """Initialize jax.distributed from explicit args or the environment.

    Resolution order per value:
      1. explicit argument,
      2. JAX-style env (`JAX_COORDINATOR_ADDRESS`, `JAX_NUM_PROCESSES`,
         `JAX_PROCESS_ID`),
      3. launcher env (`DMLC_PS_ROOT_URI:DMLC_PS_ROOT_PORT+1`,
         `DMLC_NUM_WORKER`, `DMLC_RANK`).

    No-op (single process) when nothing is configured.  Returns the number
    of processes in the job.
    """
    global _initialized
    if _initialized:
        return jax.process_count()

    coordinator = (coordinator
                   or os.environ.get("JAX_COORDINATOR_ADDRESS")
                   or _dmlc_coordinator())
    if coordinator is None:
        return 1  # single host; nothing to do

    if num_processes is None:
        num_processes = int(
            os.environ.get("JAX_NUM_PROCESSES")
            or os.environ.get("DMLC_NUM_WORKER", "1"))
    if process_id is None:
        process_id = int(
            os.environ.get("JAX_PROCESS_ID")
            or os.environ.get("DMLC_RANK", "0"))
    if not (0 <= process_id < num_processes):
        raise MXNetError(
            "init_from_env: process_id %d out of range [0, %d)"
            % (process_id, num_processes))
    logging.info("jax.distributed: %s rank %d/%d", coordinator, process_id,
                 num_processes)
    # Multi-process over the CPU backend (the localhost test/dev story,
    # like the reference's multi-process-localhost PS tests) needs a real
    # cross-process collectives implementation; without it every process
    # sees only its own devices and process_count() stays 1.  Set both the
    # env default (read at backend init) and the live config.  Only the
    # CPU backend reads this, so it is harmless on TPU jobs.
    os.environ.setdefault("JAX_CPU_COLLECTIVES_IMPLEMENTATION", "gloo")
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id)
    _initialized = True
    # Establish the cross-process collectives context NOW, while every
    # process is aligned at the same point (they all just left the same
    # initialize rendezvous).  The context bring-up has a hard ~30s peer
    # deadline; if it is instead first triggered by a real program, two
    # processes whose compile times skew by more than that spuriously time
    # out (easy on a loaded single-core host).
    barrier("mxnet_tpu.multihost.init")
    return num_processes


def barrier(name="mxnet_tpu.barrier"):
    """Block until every process reaches this point (and, first time,
    bring up the cross-process collectives contexts).  The SPMD analogue
    of the kvstore barrier.

    Two warm-ups on purpose: `sync_global_devices` establishes the
    process-level (one rank per host) context, and the tiny sharded
    reduce below establishes the device-level (one rank per device)
    context that real SPMD programs use — each has its own peer
    rendezvous with the same hard deadline."""
    try:
        import numpy as _np
        from jax.experimental import multihost_utils
        from jax.sharding import NamedSharding, PartitionSpec

        multihost_utils.sync_global_devices(name)
        if jax.process_count() > 1:
            from .mesh import make_mesh

            mesh = make_mesh(shape=(jax.device_count(),),
                             axis_names=("_barrier",),
                             devices=jax.devices())
            x = jax.device_put(
                _np.ones((jax.device_count(),), _np.float32),
                NamedSharding(mesh, PartitionSpec("_barrier")))
            jax.block_until_ready(jax.jit(lambda a: a.sum())(x))
    except Exception as e:
        logging.warning("multihost barrier %r failed: %s", name, e)


def _dmlc_coordinator():
    uri = os.environ.get("DMLC_PS_ROOT_URI")
    if not uri:
        return None
    # the PS itself owns DMLC_PS_ROOT_PORT; the jax coordinator takes +1
    port = int(os.environ.get("DMLC_PS_ROOT_PORT", "9091")) + 1
    return "%s:%d" % (uri, port)


def global_mesh(axis_names=("data",), shape=None):
    """A Mesh over every device in the (possibly multi-host) job."""
    from .mesh import make_mesh

    return make_mesh(shape=shape, axis_names=axis_names,
                     devices=jax.devices())
