"""The plain reference's loss, evaluator and blocks (fedbench/reference.py)
on labels of any rank, worked out by hand."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedbench import reference as R

CLASSES = 7
CFG = {"local": {"epochs": 1, "batch_size": 4, "optimizer": "sgd",
                 "learning_rate": 0.1}}


def apply(params, x, cfg):
    """A linear model over the last axis of x: (..., F) -> (..., C)."""
    return x @ params["w"] + params["b"]


def params(features=5, seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return {"w": jax.random.normal(k1, (features, CLASSES)),
            "b": jax.random.normal(k2, (CLASSES,))}


def data(label_shape, features=5, seed=1):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=label_shape + (features,)).astype(np.float32)
    y = rng.integers(0, CLASSES, size=label_shape).astype(np.int32)
    return x, y


def by_hand(p, x, y):
    """Mean per-label cross-entropy and accuracy, in float64 numpy."""
    logits = (x.astype(np.float64) @ np.asarray(p["w"], np.float64)
              + np.asarray(p["b"], np.float64))
    logp = logits - logits.max(-1, keepdims=True)
    logp = logp - np.log(np.exp(logp).sum(-1, keepdims=True))
    ce = -np.take_along_axis(logp, y[..., None], axis=-1)[..., 0]
    return ce.mean(), (logits.argmax(-1) == y).mean()


@pytest.mark.parametrize("label_shape", [(4,), (4, 6)],
                         ids=["per_sample", "per_token"])
def test_training_loss_is_the_mean_over_every_label(label_shape):
    """One client, one batch of 4 samples, every step frozen: the trainer's
    mean loss is the cross-entropy at the params it was given."""
    p = params()
    x, y = data(label_shape)
    trainer = R.make_trainer(apply, CFG, frozen=True)
    (out, loss), = R.train_clients(trainer, p, [(x, y, 0)], CFG, block=1)
    want, _ = by_hand(p, x, y)
    assert loss == pytest.approx(want, rel=1e-6)
    assert all(bool(jnp.array_equal(a, b)) for a, b in zip(
        jax.tree_util.tree_leaves(out), jax.tree_util.tree_leaves(p)))


@pytest.mark.parametrize("label_shape", [(9,), (9, 6)],
                         ids=["per_sample", "per_token"])
def test_evaluator_counts_every_label(label_shape):
    p = params()
    x, y = data(label_shape, seed=2)
    acc, loss = R.evaluate(R.make_evaluator(apply, CFG), p, x, y)
    want_loss, want_acc = by_hand(p, x, y)
    assert acc == want_acc
    assert loss == pytest.approx(want_loss, rel=1e-6)


def test_one_label_per_sample_is_todays_formula():
    """On 1-D labels the loss and the evaluator trace to the same programs
    as the per-sample formulas they replace, so they give the same bits."""
    logp = jnp.zeros((4, CLASSES))
    y = jnp.zeros((4,), jnp.int32)

    def today_loss(logits, y):
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))

    def ev(p, x, y):
        logits = apply(R.cast(p, R.F32), x, CFG)
        logp = jax.nn.log_softmax(logits).astype(R.F32)
        ce = -jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0]
        return jnp.sum(jnp.argmax(logits, axis=-1) == y), jnp.sum(ce)

    assert str(jax.make_jaxpr(R.mean_ce)(logp, y)) == str(
        jax.make_jaxpr(today_loss)(logp, y))
    p = params()
    x, y1 = data((4,))
    today = jax.jit(ev)         # named as make_evaluator's program is
    ev = R.make_evaluator(apply, CFG)
    assert str(jax.make_jaxpr(ev)(p, x, y1)) == str(
        jax.make_jaxpr(today)(p, x, y1))
    got, want = ev(p, x, y1), today(p, x, y1)
    assert all(bool(jnp.array_equal(a, b)) for a, b in zip(got, want))


def test_blocks_spread_over_devices_give_the_same_clients():
    """Eleven clients in blocks of 4, on one device or handed out over
    two (here the same CPU twice): the same params and losses, bit for
    bit, in the order given."""
    p = params()
    clients = [(*data((8,), seed=s), 100 + s) for s in range(11)]
    trainer = R.make_trainer(apply, CFG)
    one = R.train_clients(trainer, p, clients, CFG, block=4)
    dev = jax.devices()[0]
    two = R.train_clients(trainer, p, clients, CFG, block=4,
                          devices=[dev, dev])
    for (pa, la), (pb, lb) in zip(one, two):
        assert la == lb
        assert all(bool(jnp.array_equal(a, b)) for a, b in zip(
            jax.tree_util.tree_leaves(pa), jax.tree_util.tree_leaves(pb)))
    assert len({la for _, la in one}) == len(clients)
