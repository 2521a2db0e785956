"""Which clients the straggler scenario makes slow or crashing.

The paper's scenario (FedLesScan section VI-A4): at the start of a study
a fixed fraction of the clients is drawn to straggle, the first
`slow_share` of them slow (their work takes `slow_factor` times as long)
and the rest crashing (never answering).  The draw is the program's
`fl/experiment.make_straggler_profiles`, copied here so that the
correctness check decides who may merge without asking the program.
"""
from __future__ import annotations

from typing import Dict, Iterable

import numpy as np

HEALTHY, SLOW, CRASH = "healthy", "slow", "crash"


def profiles(client_ids: Iterable[str], traffic: dict,
             seed: int) -> Dict[str, str]:
    """client id -> HEALTHY, SLOW or CRASH, for a study run with `seed`."""
    rng = np.random.default_rng(seed)
    ids = list(client_ids)
    n_strag = int(round(traffic["straggler_fraction"] * len(ids)))
    chosen = rng.choice(ids, size=n_strag, replace=False) if n_strag else []
    n_slow = int(round(n_strag * traffic["slow_share"]))
    out = {cid: HEALTHY for cid in ids}
    for i, cid in enumerate(chosen):
        out[str(cid)] = SLOW if i < n_slow else CRASH
    return out
