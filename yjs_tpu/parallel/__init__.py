"""Device-mesh parallelism: shard the doc batch across TPU cores."""

from .mesh import (  # noqa: F401
    doc_mesh,
    shard_meshes,
)
