"""REAL multi-process distributed tests: two OS processes join the JAX
coordination service over localhost and compute/train together (reference
analog: tests/multi_gpu_tests.sh with NUM_NODES>1 over mpirun — the
reference only exercises this on a real cluster in CI; here the
coordination service runs cross-process on one machine, exercising
runtime/distributed.py and Executor.shard_batch end to end)."""
import os
import socket
import subprocess
import sys

import pytest

WORKER = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np

from flexflow_tpu.runtime import distributed

coord, pid = sys.argv[1], int(sys.argv[2])
distributed.initialize(coordinator_address=coord, num_processes=2,
                       process_id=pid)
info = distributed.host_info()
assert info["process_count"] == 2, info
assert info["global_devices"] == 4, info  # 2 hosts x 2 local CPU devices

# a global computation across both processes: sum over all 4 devices
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

mesh = distributed.pod_mesh({"data": 4})
arr = jax.make_array_from_process_local_data(
    NamedSharding(mesh, P("data")),
    np.ones((2,), np.float32) * (pid + 1),  # host 0 holds [1,1], host 1 [2,2]
    (4,),
)

@jax.jit
def total(x):
    return jnp.sum(x)

t = float(np.asarray(jax.device_get(total(arr))))
assert t == 6.0, t  # 1+1+2+2 summed across hosts
print(f"proc {pid} OK total={t}", flush=True)
distributed.shutdown()
"""

FIT_WORKER = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np

from flexflow_tpu.runtime import distributed

coord, pid = sys.argv[1], int(sys.argv[2])
distributed.initialize(coordinator_address=coord, num_processes=2,
                       process_id=pid)

import flexflow_tpu as ff

config = ff.FFConfig()
config.batch_size = 8
config.allow_mixed_precision = False
model = ff.FFModel(config)
x = model.create_tensor([8, 16], ff.DataType.DT_FLOAT)
t = model.dense(x, 32, ff.ActiMode.AC_MODE_RELU)
model.softmax(model.dense(t, 4))
model.compile(optimizer=ff.AdamOptimizer(model, alpha=0.01),
              loss_type=ff.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
              metrics=[ff.MetricsType.METRICS_ACCURACY],
              parallel_axes={"data": 4})  # spans both processes

rng = np.random.RandomState(0)  # SAME global data on both hosts
X = rng.randn(64, 16).astype(np.float32)
Y = np.argmax(X @ rng.randn(16, 4), axis=1).astype(np.int32)[:, None]
losses = [model.fit(x=X, y=Y, epochs=1, verbose=False)[-1]["loss"]
          for _ in range(6)]
assert losses[-1] < losses[0], losses
print(f"proc {pid} FIT OK {losses[0]:.4f}->{losses[-1]:.4f}", flush=True)
distributed.shutdown()
"""


def _run_two_workers(tmp_path, script_text, marker, timeout=240):
    """Launch the same worker script as process 0 and 1 with a fresh
    coordinator port; assert both exit 0 and print `marker`."""
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    coord = f"127.0.0.1:{port}"

    script = tmp_path / "worker.py"
    script.write_text(script_text)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), coord, str(pid)],
            env=env, cwd=root,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        for pid in (0, 1)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            outs.append(out)
        for p, out in zip(procs, outs):
            assert p.returncode == 0, out[-2000:]
        for pid in (0, 1):
            assert any(f"proc {pid} {marker}" in o for o in outs), outs
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()


def test_two_process_coordination_service(tmp_path):
    _run_two_workers(tmp_path, WORKER, "OK")


def test_two_process_ffmodel_fit(tmp_path):
    """FFModel.fit trains with the data axis spanning TWO processes —
    shard_batch assembles per-host addressable shards (the MULTI-NODE.md
    launch contract, executed for real)."""
    _run_two_workers(tmp_path, FIT_WORKER, "FIT OK")
