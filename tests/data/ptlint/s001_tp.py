"""PT-S001 true positives: literal PartitionSpec layout decisions at
sharding call sites — a direct literal handed to a consumer, and
tainted assignments whose spec reaches shard_map/jit shardings — all
bypassing the committed shard plan (shardplan.json).

Lint fixture — parsed by ptlint, never executed.
"""
import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from paddle_tpu.parallel import shard_map


def constrain(x, mesh):
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P("dp", None)))  # expect: PT-S001


def mapped(fn, mesh):
    spec = P(None, None, "sp", None)  # expect: PT-S001
    return shard_map(fn, mesh=mesh, in_specs=(spec,) * 3,
                     out_specs=spec)


def jitted(fn):
    batch = P("dp")  # expect: PT-S001
    return jax.jit(fn, in_shardings=(batch,), out_shardings=batch)
