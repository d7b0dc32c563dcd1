"""SEEDED VIOLATIONS: the deprecated experimental shard_map import, a
jax.shard_map reference outside parallel/mesh.py, and raw Mesh
construction outside parallel/mesh.py."""
import jax
from jax.sharding import Mesh
from jax.experimental.shard_map import shard_map

f = jax.shard_map(lambda x: x, mesh=None, in_specs=None, out_specs=None)
m = Mesh([], ("data",))
