"""Mesh / sharding helpers.

  sharding — Runtime (a mesh and its data-parallel axes) and the
             logical-axis -> PartitionSpec mapping with divisibility
             fallbacks
"""

from repro.dist.sharding import (  # noqa: F401
    Runtime,
    logical_to_spec,
)
