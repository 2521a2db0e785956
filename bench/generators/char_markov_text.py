"""Shakespeare-shaped client data, made as LEAF makes it: one text per
speaker, and each sample an 80-character window with the character that
follows it as the label.

The text comes from a sparse Markov chain over the vocabulary (the
transition matrix of the program's `data/synthetic.make_char_lm`, copied
here), so an LSTM can lower its loss.  All speakers' texts are drawn in
one vectorised pass, one step per character; the train and test windows
of a speaker come from two stretches of its text.
"""
from __future__ import annotations

import numpy as np


def generate(config: dict, traffic: dict, seed: int):
    """-> (train, test): lists of (x, y) numpy pairs, one per client."""
    vocab, seq = config["vocab"], config["seq_len"]
    n_clients = traffic["population"]
    n_train, n_test = traffic["train_per_client"], traffic["test_per_client"]
    rng = np.random.default_rng(seed)
    trans = rng.dirichlet(np.full(vocab, config["data"]["concentration"]),
                          size=vocab)
    cdf = np.cumsum(trans, axis=1)
    length = (n_train + seq) + (n_test + seq)
    text = np.empty((n_clients, length), dtype=np.int32)
    state = rng.integers(0, vocab, size=n_clients)
    u = rng.random((length, n_clients))
    for t in range(length):
        text[:, t] = state
        state = np.minimum((u[t][:, None] >= cdf[state]).sum(axis=1),
                           vocab - 1)

    def windows(part, n):
        win = np.lib.stride_tricks.sliding_window_view(part, seq, axis=1)
        return [(np.ascontiguousarray(win[c, :n]),
                 np.ascontiguousarray(part[c, seq:seq + n]))
                for c in range(n_clients)]

    return (windows(text[:, :n_train + seq], n_train),
            windows(text[:, n_train + seq:], n_test))
