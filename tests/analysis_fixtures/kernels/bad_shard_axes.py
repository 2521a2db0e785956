"""JAX004 fixture: shard_map / psum axis names that no sharding/rules.py
declares (the corpus has no such module, so the vocabulary is empty)."""
import jax
from jax.sharding import PartitionSpec as P


def sharded_sum(mesh, x):
    f = jax.shard_map(lambda a: a.sum(), mesh=mesh,
                      in_specs=(P("cohort"),),
                      out_specs=P())
    return f(x)


def cross_device_total(x):
    return jax.lax.psum(x, "workers")
