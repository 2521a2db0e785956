"""The paper's client models (§VI-A2) learn their synthetic tasks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import (make_char_lm, make_image_classification,
                        make_speech_commands)
from repro.data.synthetic import ArrayDataset
from repro.fl.tasks import ClassificationTask, TaskConfig
from repro.models.small import (SMALL_MODELS, _lstm_scan, make_char_lstm,
                                make_cnn, make_speech_cnn)


def _split(ds, n_test):
    return (ArrayDataset(ds.x[:-n_test], ds.y[:-n_test]),
            ArrayDataset(ds.x[-n_test:], ds.y[-n_test:]))


def test_registry_builds():
    for name, fn in SMALL_MODELS.items():
        model = fn()
        params = model.init(jax.random.PRNGKey(0))
        assert params, name


def test_cnn_learns_images():
    train, test = _split(make_image_classification(1200, 14, 5, seed=0), 200)
    task = ClassificationTask(make_cnn(14, 1, 5, 64),
                              TaskConfig(epochs=3, batch_size=32))
    p, _ = task.local_train(task.init_params(0), train, seed=0)
    acc, _ = task.evaluate(p, test)
    assert acc > 0.8


def test_speech_cnn_learns_keywords():
    train, test = _split(make_speech_commands(1000, 16, 16, 6, seed=0), 200)
    task = ClassificationTask(make_speech_cnn(16, 16, 6),
                              TaskConfig(epochs=4, batch_size=32))
    p, _ = task.local_train(task.init_params(0), train, seed=0)
    acc, _ = task.evaluate(p, test)
    assert acc > 0.6


def test_lstm_beats_uniform_char_prediction():
    vocab = 40
    train, test = _split(make_char_lm(1500, seq_len=20, vocab=vocab,
                                      seed=0), 300)
    task = ClassificationTask(
        make_char_lstm(vocab=vocab, embed=8, hidden=64),
        TaskConfig(epochs=3, batch_size=32, learning_rate=1e-2))
    p, _ = task.local_train(task.init_params(0), train, seed=0)
    _, loss = task.evaluate(p, test)
    assert loss < np.log(vocab) * 0.8       # clearly under uniform entropy


def test_dropout_changes_speech_output():
    model = make_speech_cnn(16, 16, 6)
    params = model.init(jax.random.PRNGKey(0))
    x = jnp.ones((2, 16, 16, 1))
    clean = model.apply(params, x)
    noisy = model.apply(params, x, dropout_rng=jax.random.PRNGKey(1))
    assert not np.allclose(clean, noisy)


# ------------------------------------------------- LSTM layer vs plain LSTM
def _plain_lstm(p, xs):
    """Per-timestep LSTM, (T, B, n_in) → (T, B, H): every product inside
    the loop, gate order (i, f, g, o), +1.0 on the forget gate."""
    hidden = p["wh"].shape[0]

    def step(carry, x_t):
        h, c = carry
        z = x_t @ p["wx"] + h @ p["wh"] + p["b"]
        i, f = z[:, :hidden], z[:, hidden:2 * hidden]
        g, o = z[:, 2 * hidden:3 * hidden], z[:, 3 * hidden:]
        c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (h, c), h

    zero = jnp.zeros((xs.shape[1], hidden), xs.dtype)
    return jax.lax.scan(step, (zero, zero), xs)[1]


def _lstm_case(n_clients=None, B=4, T=12, n_in=8, hidden=16):
    """Layer params, inputs and a fixed output weighting; with
    ``n_clients`` every leaf gets a leading client axis."""
    lead = () if n_clients is None else (n_clients,)
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    p = {"wx": jax.random.normal(ks[0], lead + (n_in, 4 * hidden)) * 0.4,
         "wh": jax.random.normal(ks[1], lead + (hidden, 4 * hidden)) * 0.3,
         "b": jax.random.normal(ks[2], lead + (4 * hidden,)) * 0.1}
    xs = jax.random.normal(ks[3], lead + (T, B, n_in))
    w = jax.random.normal(ks[4], lead + (T, B, hidden))
    return p, xs, w


def _rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


@pytest.mark.parametrize("mapped", [False, True], ids=["alone", "vmap3"])
def test_lstm_layer_forward_matches_plain_lstm(mapped):
    p, xs, _ = _lstm_case(3 if mapped else None)
    layer, plain = _lstm_scan, _plain_lstm
    if mapped:
        layer, plain = jax.vmap(layer), jax.vmap(plain)
    got, want = jax.jit(layer)(p, xs), jax.jit(plain)(p, xs)
    assert got.shape == want.shape
    assert _rel(got, want) <= 1e-6


@pytest.mark.parametrize("where", ["alone", "vmap3", "outer_scan"])
def test_lstm_layer_grads_match_plain_lstm(where):
    p, xs, w = _lstm_case(3 if where != "alone" else None)

    def grads(layer, p, xs, w):
        def loss(p, xs, w):
            return jnp.sum(jnp.tanh(layer(p, xs)) * w)

        g = jax.grad(loss, argnums=(0, 1))
        if where == "vmap3":
            return jax.vmap(g)(p, xs, w)
        if where == "outer_scan":
            # a few SGD steps, one client per step, as the executor's
            # local-step scan runs the model
            def step(q, batch):
                gp, gx = g(q, *batch)
                return jax.tree.map(lambda a, d: a - 0.1 * d, q, gp), (gp, gx)

            q0 = jax.tree.map(lambda a: a[0], p)
            return jax.lax.scan(step, q0, (xs, w))[1]
        return g(p, xs, w)

    grads = jax.jit(grads, static_argnums=0)
    got, want = grads(_lstm_scan, p, xs, w), grads(_plain_lstm, p, xs, w)
    (gp, gx), (wp, wx) = got, want
    for k in ("wx", "wh", "b"):
        assert _rel(gp[k], wp[k]) <= 1e-5, k
    assert _rel(gx, wx) <= 1e-5


def _time_scans(jaxpr, found):
    """(reverse, dot_generals in the body) of every scan, nested ones too."""
    for eqn in jaxpr.eqns:
        subs = [v for v in eqn.params.values()
                for v in (v if isinstance(v, (list, tuple)) else [v])]
        if eqn.primitive.name == "scan":
            body = eqn.params["jaxpr"].jaxpr
            found.append((eqn.params["reverse"],
                          sum(e.primitive.name == "dot_general"
                              for e in body.eqns)))
        for sub in subs:
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                _time_scans(sub, found)
    return found


def test_lstm_layer_sums_gradients_in_plain_autodiff_order():
    """Op by op, the layer's loss and gradients equal plain autodiff of the
    per-step LSTM bit for bit: only the input products leave the loop, and
    the weight and bias gradients are summed step by step in the same
    order, so a long run of local steps rounds alike."""
    p, xs, w = _lstm_case()

    def value_and_grads(layer):
        def loss(p, xs):
            return jnp.sum(jnp.tanh(layer(p, xs)) * w)

        with jax.disable_jit():
            return jax.value_and_grad(loss, argnums=(0, 1))(p, xs)

    got, want = value_and_grads(_lstm_scan), value_and_grads(_plain_lstm)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_char_lstm_time_loops_carry_no_input_product():
    model = make_char_lstm(vocab=12, embed=4, hidden=8)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, 10), jnp.int32)

    def loss(p):
        return jnp.sum(jax.nn.log_softmax(model.apply(p, tokens))[:, 0])

    scans = _time_scans(jax.make_jaxpr(jax.value_and_grad(loss))(params).jaxpr,
                        [])
    # per layer: a forward scan with h @ wh alone, and a reverse scan with
    # dg @ wh.T and the two weight-gradient sums h.T @ dg and x.T @ dg;
    # x @ wx and dg @ wx.T run outside, one product over all T·B positions
    assert sorted(scans) == [(False, 1), (False, 1), (True, 3), (True, 3)]
