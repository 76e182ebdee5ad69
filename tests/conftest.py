"""Test configuration: 8 fake CPU devices for distributed tests.

The reference needs >=2 real GPUs and torchrun for its distributed tests
(tests/test_utilities.py in /root/reference); here every topology test runs
on a virtual CPU mesh. The tier-1 command sets JAX_PLATFORMS=cpu; force_cpu
below also asks for the eight devices before any backend initializes.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from megatron_tpu.platform import force_cpu  # noqa: E402

force_cpu(8)

# The suite runs with JAX's persistent compile cache OFF, in this process
# and (through the environment) in every child a test starts:
#   * a compile for a described chip (tests/test_chip_compile.py) writes
#     entries that cannot be read back without the chip, and every later
#     lookup warns;
#   * the entry points now place the cache at a fixed path in the checkout
#     (megatron_tpu/platform.py enable_compile_cache): children of
#     different tests would meet each other's entries there, and the fault
#     tests SIGKILL children mid-write;
#   * on this host every XLA:CPU cache READ prints a page of machine-
#     feature warnings.
# The one-off in-process write-then-read crash of the old XLA:CPU did not
# reproduce on jax 0.9 (8 entries written and re-read in one process), so
# it is not the reason any more. Tests of the cache itself
# (tests/test_prefetch.py, tests/test_platform.py) turn it back on for
# their own children.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: multi-minute test (subprocess compiles etc.)")


import pytest  # noqa: E402


@pytest.fixture
def jax_cluster(tmp_path):
    """Shared harness: run N REAL jax.distributed CPU worker processes.

    Replaces test_multihost.py's bespoke spawning (and its blanket skip
    story) for everything that does NOT need cross-process XLA programs:
    the coordination-service KV store, barriers, and the
    training/coordination.py protocols all work for real on CPU — only
    cross-process *computations* (device_put to a non-addressable
    sharding) are unimplemented in this XLA:CPU.

    Usage: `rcs_outs = jax_cluster(body_src, nprocs=2)` — `body_src` runs
    in each worker after jax.distributed is initialized, with `pid`
    (process id) in scope; returns [(returncode, output), ...].
    """
    import socket
    import subprocess

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def run(body_src, nprocs=2, devices_per_proc=2, timeout=240,
            env_extra=None):
        with socket.socket() as s:
            s.bind(("localhost", 0))
            port = s.getsockname()[1]
        prologue = f"""
import os, sys
sys.path.insert(0, {repo!r})
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count={devices_per_proc}")
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
pid = int(sys.argv[1])
jax.distributed.initialize(coordinator_address="localhost:{port}",
                           num_processes={nprocs}, process_id=pid)
"""
        script = tmp_path / "cluster_worker.py"
        script.write_text(prologue + body_src)
        env = {k: v for k, v in os.environ.items()
               if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
        env.update(env_extra or {})
        procs = [subprocess.Popen(
            [sys.executable, str(script), str(i)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env) for i in range(nprocs)]
        out = []
        for p in procs:
            try:
                stdout, _ = p.communicate(timeout=timeout)
            except subprocess.TimeoutExpired:
                p.kill()
                stdout, _ = p.communicate()
            out.append((p.returncode, stdout))
        return out

    return run


@pytest.fixture(autouse=True, scope="module")
def _bound_live_executables():
    """Free compiled executables between test modules.

    The full suite compiles many hundreds of XLA:CPU programs; keeping
    them all live eventually aborts the process mid-run (raw SIGABRT in
    an execution wait, order-dependent — observed at ~60% of the suite
    once it grew past ~350 tests; every module passes standalone).
    Cross-module cache hits are rare, so this costs little."""
    yield
    import jax

    jax.clear_caches()
