"""Integration: the pjit pretraining driver trains a reduced assigned
arch end to end (sharded init → jit train steps → checkpoint restore)."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.data.synthetic import make_token_lm
from repro.launch.mesh import make_host_mesh
from repro.models import make_train_step
from repro.sharding import opt_specs, param_specs, to_named

REPO_ROOT = Path(__file__).resolve().parents[1]


def test_pretrain_loss_decreases(tmp_path):
    cfg = get_config("mamba2-130m").reduced().replace(
        efficient_ce=True, learning_rate=1e-3)
    mesh = make_host_mesh()
    train_step, init_state = make_train_step(cfg)
    rng = jax.random.PRNGKey(0)

    with mesh:
        state_struct = jax.eval_shape(lambda: init_state(rng))
        p_specs = param_specs(state_struct["params"], mesh)
        state_specs = {"params": p_specs,
                       "opt": opt_specs(state_struct["opt"], p_specs, mesh)}
        state_sh = to_named(state_specs, mesh)
        state = jax.jit(init_state, out_shardings=state_sh)(rng)

        data = make_token_lm(20_000, vocab=cfg.vocab, seq_len=32, seed=0)
        jit_step = jax.jit(train_step, donate_argnums=(0,))

        losses = []
        ckpt = CheckpointManager(str(tmp_path), keep=2)
        for step in range(30):
            idx = (np.arange(8) + step * 8) % data.x.shape[0]
            batch = {"tokens": jnp.asarray(data.x[idx]),
                     "labels": jnp.asarray(data.y[idx])}
            state, loss = jit_step(state, batch)
            losses.append(float(loss))
        ckpt.save(state, 30)

    assert np.mean(losses[-5:]) < np.mean(losses[:5]) * 0.85
    restored = ckpt.restore(jax.tree_util.tree_map(np.asarray, state))
    leaf = jax.tree_util.tree_leaves(restored)[0]
    assert np.all(np.isfinite(np.asarray(leaf, np.float32)))


def test_pretrain_cli_smoke():
    cmd = [sys.executable, "-m", "repro.launch.pretrain",
           "--arch", "gemma2-2b", "--steps", "6", "--batch", "4",
           "--seq", "32", "--log-every", "3"]
    res = subprocess.run(cmd, capture_output=True, text=True, timeout=600,
                         cwd=str(REPO_ROOT),
                         env={**os.environ, "PYTHONPATH": "src"})
    assert res.returncode == 0, res.stderr[-2000:]
    assert "final: loss" in res.stdout
