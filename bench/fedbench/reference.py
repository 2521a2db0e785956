"""Plain references for the three layers that `correct` covers: a client's
local training, the server's staleness-weighted merge, and evaluation.

Straightforward jax.numpy over the configuration's own reference model
(`configs/<name>.py`).  Nothing of the program is imported.  The float32
reference runs at the matmul precision the configuration states
(`precision.matmul`); the control runs the same code with every array in
bfloat16.  Local training runs clients in blocks of BLOCK (jax.vmap over
clients of one size), each client on its own data and sample order.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = jnp.float32
BLOCK = 8


def precision_scope(dtype, precision: str):
    """float32: matmuls at `precision` ("default", "highest", ...); the
    bfloat16 control runs at the default, which is bfloat16 already."""
    if dtype == F32:
        return jax.default_matmul_precision(precision)
    return contextlib.nullcontext()


def cast(tree, dtype):
    return jax.tree_util.tree_map(lambda a: a.astype(dtype), tree)


def client_order(n: int, batch: int, epochs: int, seed: int):
    """One client's sample order as a data loader draws it: per epoch a
    fresh `default_rng(seed).permutation(n)`, cut into batches of `batch`
    with a shorter last one.  -> full (E, n // batch, batch) and partial
    (E, n % batch) index arrays."""
    rng = np.random.default_rng(seed)
    nb = n // batch
    full, part = [], []
    for _ in range(epochs):
        perm = rng.permutation(n)
        full.append(perm[:nb * batch].reshape(nb, batch))
        part.append(perm[nb * batch:])
    return np.stack(full), np.stack(part)


def _optimizer(local: dict, dtype):
    lr = local["learning_rate"]
    if local["optimizer"] == "sgd":
        def init(p):
            return ()

        def step(p, g, state):
            return jax.tree_util.tree_map(
                lambda w, d: (w - jnp.asarray(lr, dtype) * d).astype(dtype),
                p, g), state
        return init, step
    if local["optimizer"] != "adam":
        raise ValueError(f"no reference optimizer {local['optimizer']!r}")
    b1, b2, eps = local["adam_b1"], local["adam_b2"], local["adam_eps"]

    def init(p):
        z = jax.tree_util.tree_map(jnp.zeros_like, p)
        return (z, z, jnp.zeros((), F32))

    def step(p, g, state):
        m, v, t = state
        t = t + 1.0
        m = jax.tree_util.tree_map(
            lambda a, d: (b1 * a + (1 - b1) * d).astype(dtype), m, g)
        v = jax.tree_util.tree_map(
            lambda a, d: (b2 * a + (1 - b2) * d * d).astype(dtype), v, g)
        c1 = (1.0 - b1 ** t).astype(dtype)
        c2 = (1.0 - b2 ** t).astype(dtype)
        p = jax.tree_util.tree_map(
            lambda w, a, b: (w - jnp.asarray(lr, dtype) * (a / c1)
                             / (jnp.sqrt(b / c2) + jnp.asarray(eps, dtype))
                             ).astype(dtype), p, m, v)
        return p, (m, v, t)
    return init, step


def make_trainer(apply, cfg: dict, dtype=F32, half_batch: bool = False,
                 frozen: bool = False):
    """jit(params, xf, yf, xp, yp) -> (params after local training, mean
    training loss over its steps), for a block of clients that start
    from the same params: every output and data argument has a leading
    client axis.  Per client, xf/yf hold the full batches of every
    epoch (E, nb, B, ...), xp/yp each epoch's shorter last batch (E, r,
    ...), which is skipped when r is 0.  Each step's loss is the mean
    cross-entropy over the samples of that batch.  Two planted faults:
    `half_batch`, each step keeps only the first half of its batch;
    `frozen`, each step returns the params it was given."""
    opt_init, opt_step = _optimizer(cfg["local"], dtype)

    def loss_fn(params, x, y):
        if half_batch:
            keep = max(1, x.shape[0] // 2)
            x, y = x[:keep], y[:keep]
        logp = jax.nn.log_softmax(apply(params, x, cfg))
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))

    grad = jax.value_and_grad(loss_fn)

    def step(carry, batch):
        params, state, total = carry
        x, y = batch
        loss, g = grad(params, x, y)
        if not frozen:
            params, state = opt_step(params, g, state)
        return (params, state, total + loss.astype(F32)), None

    def train(params, xf, yf, xp, yp):
        params = cast(params, dtype)
        epochs, nb = xf.shape[0], xf.shape[1]
        has_part = xp.shape[1] > 0

        def epoch(carry, batches):
            xfe, yfe, xpe, ype = batches
            carry, _ = lax.scan(step, carry, (xfe, yfe))
            if has_part:
                carry, _ = step(carry, (xpe, ype))
            return carry, None

        init = (params, opt_init(params), jnp.zeros((), F32))
        (params, _, total), _ = lax.scan(epoch, init, (xf, yf, xp, yp))
        return params, total / (epochs * (nb + int(has_part)))

    return jax.jit(jax.vmap(train, in_axes=(None, 0, 0, 0, 0)))


def train_clients(trainer, params, clients, cfg: dict, dtype=F32,
                  precision: str = "default"):
    """Local training of clients from the same `params`.  clients: a list
    of (x, y, seed).  -> a list of (params after training, mean loss), in
    the order given.  Clients of one size go through the trainer BLOCK at
    a time; a short block is filled up with copies of its last client,
    whose results are dropped."""
    local = cfg["local"]
    out = [None] * len(clients)
    by_size = {}
    for i, (x, _, _) in enumerate(clients):
        by_size.setdefault(len(x), []).append(i)
    for idx in by_size.values():
        for b0 in range(0, len(idx), BLOCK):
            block = idx[b0:b0 + BLOCK]
            fill = block + [block[-1]] * (BLOCK - len(block))
            xf, yf, xp, yp = [], [], [], []
            for i in fill:
                x, y, seed = clients[i]
                full, part = client_order(len(x), local["batch_size"],
                                          local["epochs"], seed)
                xf.append(x[full])
                yf.append(y[full])
                xp.append(x[part])
                yp.append(y[part])
            x0 = clients[block[0]][0]
            xdt = dtype if np.issubdtype(x0.dtype, np.floating) else x0.dtype
            with precision_scope(dtype, precision):
                ps, losses = trainer(
                    params, jnp.asarray(np.stack(xf), xdt),
                    jnp.asarray(np.stack(yf)), jnp.asarray(np.stack(xp), xdt),
                    jnp.asarray(np.stack(yp)))
            ps = cast(ps, F32)
            losses = np.asarray(losses)
            for j, i in enumerate(block):
                out[i] = (jax.tree_util.tree_map(lambda a, j=j: a[j], ps),
                          float(losses[j]))
    return out


def make_evaluator(apply, cfg: dict, dtype=F32):
    """jit(params, x, y) -> (correct predictions, summed cross-entropy)."""
    def ev(params, x, y):
        logits = apply(cast(params, dtype), x, cfg)
        logp = jax.nn.log_softmax(logits).astype(F32)
        ce = -jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0]
        return jnp.sum(jnp.argmax(logits, axis=-1) == y), jnp.sum(ce)
    return jax.jit(ev)


def evaluate(evaluator, params, x, y, dtype=F32, precision="default"):
    """-> (accuracy, mean loss) over every sample of (x, y)."""
    xdt = dtype if np.issubdtype(x.dtype, np.floating) else x.dtype
    with precision_scope(dtype, precision):
        c, s = evaluator(params, jnp.asarray(x, xdt), jnp.asarray(y))
    return float(c) / len(x), float(s) / len(x)


def staleness_coefficients(entries, current_round: int):
    """FedLesScan Eq. 3 over the merged updates: (t_k / t) (n_k / n) with
    1-based rounds, i.e. (t_k + 1) / (t + 1) for 0-based ones.
    entries: (producing round, samples) per update."""
    n = float(sum(s for _, s in entries))
    t = float(current_round + 1)
    return np.array([((r + 1) / t) * (s / n) for r, s in entries],
                    np.float64)


def weighted_sum(rows, coeffs, dtype=F32):
    """Sum_k c_k W_k, accumulated one update at a time in `dtype`."""
    acc = None
    for row, c in zip(rows, coeffs):
        term = jnp.asarray(c, dtype) * row.astype(dtype)
        acc = term if acc is None else (acc + term).astype(dtype)
    return acc.astype(F32)
