"""Analytic work counts.  They depend only on the configuration and the
number of updates merged, never on how the program implements the work
(its tiling, padding, buckets or fusion)."""
from __future__ import annotations


def train_flops(forward_flops: float, samples: int) -> float:
    """Model FLOPs of local training: forward plus backward (three
    forwards) for every real sample of every local epoch."""
    return 3.0 * forward_flops * samples


def merge_work(k: int, p: int):
    """(operations, bytes) of one identity merge (the FedAvg / Eq. 3
    weighted sum) of k updates of p float32 values: one multiply and one
    add per update value, 2 k p operations; the (k, p) updates and the k
    coefficients read and the (p,) result written, (k p + p + k) x 4
    bytes."""
    return 2 * k * p, (k * p + p + k) * 4


def least_time(ops: float, nbytes: float, peak_flops: float,
               peak_bw: float):
    """-> (seconds, bound): the larger of the compute time and the memory
    time at the peaks given, and which of the two it is."""
    t_ops, t_mem = ops / peak_flops, nbytes / peak_bw
    return (t_mem, "memory") if t_mem >= t_ops else (t_ops, "compute")
