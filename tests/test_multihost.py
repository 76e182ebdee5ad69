"""Multi-host mechanics on CPU: two real jax.distributed processes build
the global mesh, feed per-host batch shards, and run one training step
(counterpart of the reference's multi-node path, initialize.py:124-167 —
which needs real GPUs + torchrun; here it runs hermetically)."""

import os
import socket
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# This jax's XLA:CPU client cannot execute cross-process COMPUTATIONS: a
# device_put of a host array to a non-addressable sharding (each process
# holds only its slice of the global batch) routes through a multihost
# device broadcast that the CPU backend rejects with exactly this
# message. On a real TPU backend the same code path works; the step test
# must skip, not fail, so the suite stays green on CPU CI (a multi-host
# TPU run of it is not available: the chip tool hands out one host).
# The skip is NARROW now: everything that is not an XLA program — the
# jax.distributed coordination service, its KV store, barriers, and the
# whole training/coordination.py protocol suite — runs FOR REAL on CPU
# under the shared `jax_cluster` harness (test_two_process_host_broadcast
# below + tests/test_coordination.py), so only the device-collective step
# itself remains TPU-gated.
_CPU_MULTIHOST_UNSUPPORTED = "Multiprocess computations aren't implemented"

_WORKER = r"""
import os, sys
sys.path.insert(0, %(repo)r)
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
pid = int(sys.argv[1])

from megatron_tpu.parallel.distributed import (
    build_multihost_mesh, host_batch_slice, initialize_distributed,
    put_process_local_batch,
)
assert initialize_distributed(coordinator_address=%(coord)r,
                              num_processes=2, process_id=pid)
assert jax.process_count() == 2
assert len(jax.devices()) == 8

import jax.numpy as jnp
import numpy as np
from megatron_tpu.config import OptimizerConfig, ParallelConfig, TrainingConfig
from megatron_tpu.models import presets
from megatron_tpu.models.params import init_params, param_specs
from megatron_tpu.parallel.sharding import shard_tree
from megatron_tpu.training.optimizer import init_train_state, train_state_specs
from megatron_tpu.training.train_step import make_train_step
from jax.sharding import NamedSharding, PartitionSpec as P

par = ParallelConfig(tensor_parallel=2)
rt = build_multihost_mesh(par)
assert rt.dp == 4, rt.dp
# data axis must be outermost across processes: each host's addressable
# mesh rows are contiguous
rows = {d.process_index for d in rt.mesh.devices[:2].ravel()}
assert rows == {0}, rows

cfg = presets.tiny(vocab_size=64, seq_length=16, num_layers=2,
                   hidden_size=32, num_attention_heads=4, num_kv_heads=2,
                   ffn_hidden_size=64)
opt = OptimizerConfig(lr=1e-3, lr_decay_style="constant")
tcfg = TrainingConfig(micro_batch_size=1, global_batch_size=8, seed=0)

params = init_params(cfg, jax.random.PRNGKey(0))
params = shard_tree(rt, params, param_specs(cfg))
state = init_train_state(opt, params)
step = make_train_step(cfg, opt, tcfg, num_microbatches=2, train_iters=4)

GB = tcfg.global_batch_size
lo, hi = host_batch_slice(rt, GB)
assert (hi - lo) == GB // 2, (lo, hi)
# deterministic global batch; each host materializes only its slice
rng = np.random.default_rng(0)
tokens = rng.integers(0, 64, (GB, 16)).astype(np.int32)
labels = rng.integers(0, 64, (GB, 16)).astype(np.int32)
local = {
    "tokens": tokens[lo:hi],
    "labels": labels[lo:hi],
    "loss_mask": np.ones((hi - lo, 16), np.float32),
}
batch = put_process_local_batch(rt, local, GB)

with jax.sharding.set_mesh(rt.mesh):
    jstep = jax.jit(step, donate_argnums=(0,))
    state, metrics = jstep(state, batch)
    loss = float(metrics["loss"])
print(f"WORKER{pid} loss={loss:.6f}", flush=True)
"""


_BCAST_WORKER = r"""
import numpy as np
from megatron_tpu.training.coordination import (
    ClusterCoordinator, KVBackend)

assert jax.process_count() == 2
c = ClusterCoordinator(KVBackend(), pid, 2, peer_death_timeout_s=10,
                       poll_s=0.05)
c.topology_barrier(60)
# host-data broadcast (the multihost-utils use case for SMALL host values:
# agreed config, sampler seeds, resolved checkpoint iteration) over the
# coordination service instead of an XLA device collective — which is why
# it runs for real on XLA:CPU
payload = {"seed": 1234, "resume_iteration": 40,
           "order": list(np.arange(4).tolist())} if pid == 0 else None
got = c.broadcast(payload, root=0, key="run_cfg", timeout_s=60)
assert got == {"seed": 1234, "resume_iteration": 40, "order": [0, 1, 2, 3]}
# rendezvous so neither side tears the service down under the other
c.publish_value("done", True)
import time
deadline = time.monotonic() + 60
while c.read_value("done", host=1 - pid) is None:
    assert time.monotonic() < deadline
    time.sleep(0.05)
print(f"BCAST{pid} OK", flush=True)
"""


def test_two_process_host_broadcast(jax_cluster):
    """The broadcast this file used to skip wholesale, run FOR REAL: two
    jax.distributed CPU processes agree on one host value through the
    coordination service's KV store (training/coordination.py broadcast).
    Only the XLA *device* broadcast remains TPU-gated (test below)."""
    results = jax_cluster(_BCAST_WORKER, nprocs=2, devices_per_proc=1,
                          timeout=240)
    for i, (rc, out) in enumerate(results):
        assert rc == 0, f"worker {i} failed:\n{out}"
        assert f"BCAST{i} OK" in out


@pytest.mark.slow  # 10s measured on CPU — where it only SKIPS anyway
# (multiprocess XLA:CPU computations unimplemented; the non-XLA half of
# multihost — coordination service, KV store, host broadcast — runs for
# real above); the device-collective step needs several TPU hosts
def test_two_process_distributed_step(tmp_path):
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    coord = f"localhost:{port}"
    script = tmp_path / "worker.py"
    script.write_text(_WORKER % {"repo": REPO, "coord": coord})

    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    procs = [subprocess.Popen([sys.executable, str(script), str(i)],
                              stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                              text=True, env=env)
             for i in range(2)]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=600)
        except subprocess.TimeoutExpired:
            # the peer of a crashed worker can wedge in a collective;
            # collect what it printed and let the skip check below decide
            p.kill()
            out, _ = p.communicate()
        outs.append(out)
    if any(_CPU_MULTIHOST_UNSUPPORTED in out for out in outs):
        pytest.skip(
            "this jax's CPU backend cannot device_put to a non-addressable "
            f"sharding ({_CPU_MULTIHOST_UNSUPPORTED!r}: the per-host batch "
            "placement routes through a multihost broadcast XLA:CPU does "
            "not implement); real multi-process coverage needs several "
            "TPU hosts")
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {i} failed:\n{out}"
    losses = []
    for i, out in enumerate(outs):
        line = [ln for ln in out.splitlines() if ln.startswith(f"WORKER{i}")][0]
        losses.append(float(line.split("loss=")[1]))
    # both processes computed the same global step
    assert abs(losses[0] - losses[1]) < 1e-6
    assert np.isfinite(losses[0])
