"""Test configuration: force an 8-device CPU platform.

This is the TPU build's version of the reference's hardware fakes (SURVEY §4):
multi-device logic (DP executor groups, mesh sharding, model parallelism)
runs on 8 virtual CPU devices, the same way the reference tested
model-parallel code on cpu(0)/cpu(1).

The platform forcing (and the XLA flag rewriting) lives in
mxnet_tpu.test_utils.force_cpu_devices, shared with
``__graft_entry__.dryrun_multichip``.
"""
from mxnet_tpu.test_utils import force_cpu_devices

force_cpu_devices(8)


def pytest_configure(config):
    # the tier-1 gate deselects these (`-m 'not slow'`); tests/nightly.sh
    # runs them
    config.addinivalue_line(
        "markers",
        "slow: multi-process / long-running tests (nightly suite)")
