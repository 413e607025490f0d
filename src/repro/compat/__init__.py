"""Environment layer: mesh/sharding introspection and Pallas call settings.

``repro.compat.meshenv`` is the single point of contact for every
mesh/sharding introspection the model and launch stacks perform:
axis discovery, ambient-mesh queries, mesh construction, sharding
constraints, and shard_map.  ``repro.compat.pallascompat`` builds the
Pallas TPU compiler params and decides interpret mode from the backend.
No module outside this package may touch a gated ``jax.sharding`` /
Pallas symbol (``get_abstract_mesh``, ``AxisType``, ``set_mesh``,
``CompilerParams``, ``axis_types=``) — enforced by the compat-boundary
checker (``tests/test_compat.py``).
"""

from repro.compat import meshenv

__all__ = ["meshenv"]
