"""Real multi-process validation of the multi-host data-parallel path.

Launches TWO actual JAX processes (jax.distributed on localhost, 4
virtual CPU devices each -> an 8-device global mesh) and runs training
steps where each process feeds only its local shard of every global
batch — exercising `shard_batch`'s
``make_array_from_process_local_data`` branch and the per-process
`train_batches` sharding that single-process tests can't reach.
The replicas must report IDENTICAL losses (replicated state staying in
sync is the whole point of the DDP-equivalent design).
"""

import os
import socket
import subprocess
import sys

import pytest

_WORKER = r"""
import os, sys
proc_id = int(sys.argv[1]); port = sys.argv[2]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, {repo!r})
import jax
from fast_autoaugment_tpu.parallel.mesh import distributed_init, make_mesh, shard_batch
distributed_init(f"localhost:{{port}}", 2, proc_id)  # what --coordinator calls
import numpy as np, jax.numpy as jnp
from fast_autoaugment_tpu.models import get_model
from fast_autoaugment_tpu.ops.optim import build_optimizer
from fast_autoaugment_tpu.train.steps import create_train_state, make_train_step
from fast_autoaugment_tpu.data.pipeline import train_batches
from fast_autoaugment_tpu.data.datasets import ArrayDataset

assert jax.process_count() == 2 and len(jax.devices()) == 8
mesh = make_mesh()
model = get_model({{"type": "wresnet10_1"}}, 10)
opt = build_optimizer({{"type": "sgd", "decay": 1e-4, "clip": 5.0,
                        "momentum": 0.9, "nesterov": True}}, lambda s: 0.1)
state = create_train_state(model, opt, jax.random.PRNGKey(0),
                           jnp.zeros((2, 32, 32, 3)), use_ema=False)
step = make_train_step(model, opt, num_classes=10, use_policy=False)
rng = np.random.default_rng(0)
ds = ArrayDataset(rng.integers(0, 256, (64, 32, 32, 3), dtype=np.uint8),
                  rng.integers(0, 10, (64,), dtype=np.int32), 10)
losses = []
for images, labels in train_batches(ds, None, 16, epoch=1,
                                    process_index=proc_id, process_count=2):
    assert images.shape[0] == 8, "local shard must be global/2"
    batch = shard_batch(mesh, {{"x": images, "y": labels}})
    assert batch["x"].shape[0] == 16, "global batch must reassemble"
    state, metrics = step(state, batch["x"], batch["y"],
                          jnp.zeros((1, 1, 3), jnp.float32), jax.random.PRNGKey(1))
    losses.append(round(float(metrics["loss"]) / float(metrics["num"]), 6))
print("LOSSES", proc_id, losses, flush=True)

# eval path: each host feeds only its shard (no P-x duplicated device
# work, ADVICE round 1 medium); counts must reflect the REAL dataset
# size once, globally
from fast_autoaugment_tpu.data.pipeline import eval_batches
from fast_autoaugment_tpu.train.steps import make_eval_step
from fast_autoaugment_tpu.core.metrics import Accumulator

eval_ds = ArrayDataset(rng.integers(0, 256, (30, 32, 32, 3), dtype=np.uint8),
                       rng.integers(0, 10, (30,), dtype=np.int32), 10)
eval_step = make_eval_step(model, num_classes=10)
acc = Accumulator()
for images, labels, mask in eval_batches(eval_ds, None, 16, process_index=proc_id,
                                         process_count=2, pad_multiple=8):
    assert images.shape[0] == 8, "per-process shard of the padded global batch"
    batch = shard_batch(mesh, {{"x": images, "y": labels, "m": mask}})
    acc.add_dict(eval_step(state.params, state.batch_stats,
                           batch["x"], batch["y"], batch["m"]))
norm = acc.normalize()
assert int(acc["num"]) == 30, f"eval must count each sample once, got {{acc['num']}}"
print("EVAL", proc_id, round(norm["loss"], 6), round(norm["top1"], 6), flush=True)
"""


@pytest.mark.slow
def test_two_process_training_stays_in_sync(tmp_path):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    script = tmp_path / "worker.py"
    script.write_text(_WORKER.format(repo=repo))

    env = dict(os.environ)
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(i), str(port)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env, text=True,
        )
        for i in range(2)
    ]
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=600)
        outs.append(out)
        assert p.returncode == 0, out[-2000:]

    losses, evals = {}, {}
    for out in outs:
        for line in out.splitlines():
            if line.startswith("LOSSES"):
                _tag, pid, vals = line.split(" ", 2)
                losses[pid] = vals
            elif line.startswith("EVAL"):
                _tag, pid, vals = line.split(" ", 2)
                evals[pid] = vals
    assert set(losses) == {"0", "1"}, outs
    # replicated training state: both processes observe identical losses
    assert losses["0"] == losses["1"]
    assert "2.3" in losses["0"]  # ~ln(10) at init on random labels
    # sharded eval: both processes assemble the same global metrics
    assert set(evals) == {"0", "1"}, outs
    assert evals["0"] == evals["1"]


def test_distributed_init_raises_when_a_coordinator_was_given(monkeypatch):
    """--coordinator must never be a silent no-op: a failed rendezvous
    raises, so a host cannot train alone and report success.  Without a
    coordinator a failed auto-detection still means single-process."""
    import jax

    from fast_autoaugment_tpu.parallel import mesh

    calls = []

    def refuse(**kwargs):
        calls.append(kwargs)
        raise RuntimeError("jax.distributed.initialize() must be called "
                           "before any JAX calls")

    monkeypatch.setattr(jax.distributed, "initialize", refuse)
    monkeypatch.setattr(jax.distributed, "is_initialized", lambda: False)
    with pytest.raises(RuntimeError, match="host0:1234.*failed"):
        mesh.distributed_init("host0:1234", 2, 1)
    assert calls == [dict(coordinator_address="host0:1234", num_processes=2,
                          process_id=1)]
    mesh.distributed_init()  # no coordinator: swallowed, single-process
    assert len(calls) == 2
    # already initialised: nothing is touched again
    monkeypatch.setattr(jax.distributed, "is_initialized", lambda: True)
    mesh.distributed_init("host0:1234", 2, 1)
    assert len(calls) == 2
