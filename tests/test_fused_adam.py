"""The fused dense-weight Adam path of the vectorized executor.

A dense weight of at least `FUSED_ADAM_MIN_BYTES` per client, trained by
Adam without weight decay and without FedProx's proximal term, takes its
backward pass and Adam step in `kernels.dense_adam`, from its gradient's
factors.  A FEMNIST-
shaped CNN (28x28 images, 32 and 64 channels, so fc1 has 3,136 inputs)
trains to the same params with the path engaged and disengaged, on one
device and with the cohort sharded over four virtual CPU devices; the
``fused_adam_steps`` counter and ``fl.dispatch``'s ``fused_adam`` say
when it engaged.
"""
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import spans
from repro.data import make_image_classification
from repro.data.synthetic import ArrayDataset
from repro.fl import executor
from repro.fl.tasks import ClassificationTask, TaskConfig
from repro.models.small import make_char_lstm, make_cnn

ROOT = Path(__file__).resolve().parents[1]
# fc1 is 3136 x 384 float32, 4.8 MB: above the threshold
FC_WIDTH, CLIENTS, SAMPLES, BATCH = 384, 2, 27, 10
STEPS = -(-SAMPLES // BATCH)            # 3, the last batch 7 real samples


def _cnn_task(optimizer="adam"):
    return ClassificationTask(
        make_cnn(28, 1, 10, FC_WIDTH, "femnist_shaped"),
        TaskConfig(epochs=1, batch_size=BATCH, optimizer=optimizer))


def _datasets(image_size=28, n=CLIENTS, samples=SAMPLES):
    full = make_image_classification(n * samples, image_size=image_size,
                                     n_classes=10, seed=0)
    x, y = np.asarray(full.x), np.asarray(full.y)
    return [ArrayDataset(x[i * samples:(i + 1) * samples],
                         y[i * samples:(i + 1) * samples])
            for i in range(n)]


def _train(task, datasets, mu=0.0):
    """(stacked params, losses, fused_adam_steps added) of one dispatch."""
    ex = executor.VectorizedExecutor(task)
    before = spans.counters()["fused_adam_steps"]
    params, losses = ex._train_group(datasets, task.init_params(0), mu,
                                     list(range(len(datasets))))
    jax.block_until_ready(params)
    return params, losses, spans.counters()["fused_adam_steps"] - before


def test_engages_on_a_large_dense_weight_only():
    task = _cnn_task()
    params = task.init_params(0)
    assert executor.fused_adam_layers(task.optimizer, 0.0, params) == (
        ("fc1",),)
    # the same weight under FedProx's proximal term, or AdamW, does not
    assert executor.fused_adam_layers(task.optimizer, 0.01, params) == ()
    assert executor.fused_adam_layers(_cnn_task("adamw").optimizer, 0.0,
                                      params) == ()


@pytest.fixture(scope="module")
def engaged_and_not():
    """The same cohort trained with the path engaged and disengaged."""
    task, datasets = _cnn_task(), _datasets()
    engaged = _train(task, datasets)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(executor, "FUSED_ADAM_MIN_BYTES", 1 << 62)
        plain = _train(task, datasets)
    return engaged, plain


def test_engaged_path_trains_to_the_same_params(engaged_and_not):
    (params, losses, _), (want, want_losses, _) = engaged_and_not
    np.testing.assert_allclose(np.asarray(losses), np.asarray(want_losses),
                               rtol=1e-5, atol=1e-6)
    for (path, got), leaf in zip(
            jax.tree_util.tree_leaves_with_path(params),
            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(leaf),
                                   rtol=1e-5, atol=1e-5,
                                   err_msg=jax.tree_util.keystr(path))


def test_fused_adam_steps_counts_bucket_steps_and_weights(engaged_and_not):
    (_, _, count), (_, _, plain_count) = engaged_and_not
    assert count == CLIENTS * STEPS * 1
    assert plain_count == 0


@pytest.mark.parametrize("case", ["lstm_sgd", "fedprox_mu", "adamw"])
def test_counter_stays_zero_where_the_path_is_bypassed(case):
    if case == "lstm_sgd":
        task = ClassificationTask(
            make_char_lstm(vocab=82, embed=8, hidden=256),
            TaskConfig(epochs=1, batch_size=BATCH, optimizer="sgd",
                       learning_rate=0.8))
        rng = np.random.default_rng(0)
        datasets = [ArrayDataset(rng.integers(0, 82, (SAMPLES, 80)),
                                 rng.integers(0, 82, SAMPLES))
                    for _ in range(CLIENTS)]
    else:
        task = _cnn_task("adamw" if case == "adamw" else "adam")
        datasets = _datasets()
    _, _, count = _train(task, datasets,
                         mu=0.01 if case == "fedprox_mu" else 0.0)
    assert count == 0


FOUR_DEVICES = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys
    sys.path.insert(0, "src")
    sys.path.insert(0, "tests")
    import jax, numpy as np
    assert jax.device_count() == 4
    from repro.core import spans
    from repro.fl import executor
    from repro.launch.mesh import make_clients_mesh
    from test_fused_adam import _cnn_task, _datasets

    task, datasets = _cnn_task(), _datasets(n=4)
    params = task.init_params(0)
    seeds = [0, 1, 2, 3]
    ex = executor.VectorizedExecutor(task)
    single, _ = ex._train_group(datasets, params, 0.0, seeds)
    ex.configure_mesh(make_clients_mesh(4))
    before = spans.counters()["fused_adam_steps"]
    sharded, _ = ex._train_group(datasets, params, 0.0, seeds)
    assert len(sharded["fc1"]["w"].sharding.device_set) == 4
    assert spans.counters()["fused_adam_steps"] - before == 4 * 3
    for a, b in zip(jax.tree_util.tree_leaves(sharded),
                    jax.tree_util.tree_leaves(single)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
    print("FUSED-ADAM-SHARDED-OK")
""")


def test_cohort_sharded_over_four_devices_engages_and_matches():
    res = subprocess.run([sys.executable, "-c", FOUR_DEVICES],
                         capture_output=True, text=True, timeout=600,
                         cwd=str(ROOT))
    assert "FUSED-ADAM-SHARDED-OK" in res.stdout, res.stdout + res.stderr
