"""Mesh-size scaling tests (VERDICT round-1 item 10).

The 8-device conftest mesh can hide shape/divisibility assumptions; these
tests run the full parallelism validation (dp+tp, ring-attention sp, GPipe
pp, MoE ep — `__graft_entry__.dryrun_multichip`) at 16 and 32 virtual
devices in fresh subprocesses, plus a REAL 2-process x 4-device multihost
job (`jax.distributed` over localhost, `parallel.multihost.init_from_env`)
training one SPMD step over the joint 8-device mesh.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _fresh_env(n_devices):
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%d" % n_devices
    return env


@pytest.mark.parametrize("n", [16, 32])
def test_dryrun_multichip_scales(n):
    proc = subprocess.run(
        [sys.executable, "-c",
         "import __graft_entry__ as g; g.dryrun_multichip(%d)" % n],
        capture_output=True, text=True, timeout=560, env=_fresh_env(n),
        cwd=ROOT)
    assert proc.returncode == 0, proc.stdout[-1500:] + proc.stderr[-1500:]
    assert "dp/tp/sp/pp/ep all compiled, executed and oracle-checked" \
        in proc.stdout
    # round-6 numeric oracles: every mode prints (and gates on) its
    # sharded-vs-replica max-abs-diff — compiling is no longer passing
    for mode in ("dp+tp", "lm_ce_shard", "sp", "pp", "ep"):
        assert ("dryrun_multichip %s oracle: max_abs_diff=" % mode) \
            in proc.stdout, (mode, proc.stdout[-1500:])
    assert "vocab-sharded fused CE head" in proc.stdout


MULTIHOST_WORKER = textwrap.dedent("""
    import os
    import numpy as np
    import jax

    from mxnet_tpu.parallel import SPMDTrainer, multihost
    from mxnet_tpu import models

    nproc = multihost.init_from_env()
    assert nproc == 2, nproc
    assert jax.process_count() == 2
    assert len(jax.devices()) == 8, len(jax.devices())  # 2 hosts x 4

    mesh = multihost.global_mesh(axis_names=("data",))
    net = models.get_mlp()
    batch = 16
    trainer = SPMDTrainer(net, mesh,
                          data_shapes={"data": (batch, 784),
                                       "softmax_label": (batch,)},
                          lr=0.1, momentum=0.9)
    rng = np.random.RandomState(0)
    # each process provides its addressable shard of the global batch
    local = {
        "data": rng.randn(batch, 784).astype(np.float32),
        "softmax_label": rng.randint(0, 10, (batch,)).astype(np.float32),
    }
    trainer.step(local)
    jax.block_until_ready(trainer.params)
    print("multihost rank %d ok over %d devices"
          % (jax.process_index(), len(jax.devices())))
""")


def test_two_process_multihost_dryrun(tmp_path):
    """2 localhost processes x 4 CPU devices each: jax.distributed comes up
    from the launcher-style env and one fused SPMD step runs over the
    joint mesh."""
    import socket

    script = tmp_path / "mh_worker.py"
    script.write_text(MULTIHOST_WORKER)
    # a fresh ephemeral port: a stale coordination service from an earlier
    # run on a fixed port wedges jax.distributed in confusing ways
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    procs = []
    for rank in range(2):
        env = _fresh_env(4)
        env["JAX_COORDINATOR_ADDRESS"] = "127.0.0.1:%d" % port
        env["JAX_NUM_PROCESSES"] = "2"
        env["JAX_PROCESS_ID"] = str(rank)
        procs.append(subprocess.Popen(
            [sys.executable, str(script)], env=env, cwd=ROOT,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=540)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append((p.returncode, out))
    for rc, out in outs:
        assert rc == 0, out[-9000:]
    joined = "".join(o for _, o in outs)
    assert "multihost rank 0 ok over 8 devices" in joined
    assert "multihost rank 1 ok over 8 devices" in joined
