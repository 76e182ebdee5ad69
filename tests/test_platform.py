"""megatron_tpu/platform.py: where the compile cache goes, what is known
about the device, and the start-up of a one-host TPU machine."""

import json
import os
import subprocess
import sys
import types

import pytest

from megatron_tpu import platform

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CACHE_PROBE = """
import json, os, sys
sys.path.insert(0, {repo!r})
import jax
from megatron_tpu.platform import enable_compile_cache
before = jax.config.jax_compilation_cache_dir
placed = enable_compile_cache()
after = jax.config.jax_compilation_cache_dir
if os.environ.get("PROBE_COMPILES"):
    jax.jit(lambda x: x * 2 + 1)(jax.numpy.ones((8, 8))).block_until_ready()
print(json.dumps({{"before": before, "placed": placed, "after": after}}))
"""


def _probe(env_extra):
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(JAX_PLATFORMS="cpu", **env_extra)
    r = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE.format(repo=REPO)], env=env,
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_cache_dir_from_the_environment_is_left_alone(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: JAX already uses it, the helper
    changes nothing, and what the process compiles lands there."""
    outside = str(tmp_path / "placed_from_outside")
    got = _probe({"JAX_COMPILATION_CACHE_DIR": outside,
                  "JAX_ENABLE_COMPILATION_CACHE": "true",
                  "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
                  "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "0",
                  "PROBE_COMPILES": "1"})
    assert got == {"before": outside, "placed": outside, "after": outside}
    assert os.listdir(outside), "nothing was cached where the env said"


def test_cache_dir_defaults_to_the_checkout():
    """Unset: a fixed path inside the checkout — no temporary name, pid or
    clock in it, so a second start finds what the first compiled."""
    got = _probe({})
    assert got["before"] in (None, "")
    assert got["placed"] == got["after"] == os.path.join(REPO, ".jax_cache")
    assert got["placed"] == platform.DEFAULT_COMPILE_CACHE


def test_peak_flops_known_and_unknown_device_kinds():
    """The table is keyed on the device_kind the chip reports ("TPU v5
    lite" on the v5e, chip run of PR 21); an unknown kind is an error,
    never the v5e figure."""
    v5e = types.SimpleNamespace(device_kind="TPU v5 lite")
    assert platform.peak_bf16_flops(v5e) == 197e12
    for kind in ("TPU v7x", "cpu", "NVIDIA H100"):
        with pytest.raises(ValueError, match="no peak bf16 FLOP/s"):
            platform.peak_bf16_flops(types.SimpleNamespace(device_kind=kind))


def test_require_tpu_refuses_the_cpu():
    assert platform.device_summary() == {
        "platform": "cpu", "kind": "cpu", "count": 8}
    with pytest.raises(RuntimeError, match="needs a TPU"):
        platform.require_tpu()


@pytest.mark.parametrize("hostnames,auto,expect_call", [
    ("localhost", None, False),       # the one-chip / four-chip host
    ("", None, False),
    ("host-0,host-1", None, True),    # a pod slice: rendezvous
    ("localhost", "1", True),         # explicit opt-in
])
def test_single_host_tpu_does_not_look_for_a_coordinator(
        monkeypatch, hostnames, auto, expect_call):
    """A one-host TPU machine exports TPU_WORKER_HOSTNAMES=localhost (the
    chip machines do): that is not a pod, and start-up must not depend on
    how a bare jax.distributed.initialize() fails there."""
    import jax

    from megatron_tpu.parallel import distributed

    calls = []
    monkeypatch.setattr(jax.distributed, "initialize",
                        lambda *a, **k: calls.append((a, k)))
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", hostnames)
    for var in ("MEGATRON_TPU_COORDINATOR", "MEGATRON_TPU_NUM_PROCESSES",
                "MEGATRON_TPU_PROCESS_ID"):
        monkeypatch.delenv(var, raising=False)
    if auto is None:
        monkeypatch.delenv("MEGATRON_TPU_AUTO_DISTRIBUTED", raising=False)
    else:
        monkeypatch.setenv("MEGATRON_TPU_AUTO_DISTRIBUTED", auto)
    assert distributed.initialize_distributed() is expect_call
    assert bool(calls) is expect_call
