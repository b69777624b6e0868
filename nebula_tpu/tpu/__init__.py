"""TPU device plane: HBM-pinned CSR snapshots + sharded traversal kernels.

This package is the TPU-native replacement for the reference's storage
read hot path (per-request RocksDB prefix scans in GetNeighborsProcessor
plus the per-hop storage.thrift fan-out in StorageClient / TraverseExecutor;
reference: src/storage/query, src/clients/storage, src/graph/executor
[UNVERIFIED — empty mount, SURVEY §0]).  Design per SURVEY §7 step 5:

  * a `jax.sharding.Mesh(('part',))` with one graph partition per device;
  * the space's CSR snapshot `device_put` across the mesh (device.py);
  * a multi-hop traversal kernel under `shard_map`: per-hop local CSR
    expansion (vectorized segment gather), compiled predicate mask,
    sorted-unique dedup, hash routing + `lax.all_to_all` frontier
    re-shard over ICI (hop.py);
  * a predicate compiler lowering nGQL expression subtrees to jnp mask
    functions with exact three-valued-logic semantics (exprjit.py);
  * a runtime with power-of-two bucket escalation for dynamic frontier /
    expansion sizes (runtime.py), the driver of what crosses back
    (fetch.py) and of what the caller gets of it (assemble.py);
  * the `TpuTraverse` fused plan node: executor + optimizer rule
    (traverse.py).

Importing this package enables 64-bit mode in jax: property columns are
int64 (epoch-millisecond timestamps etc. overflow int32).
"""
import jax

jax.config.update("jax_enable_x64", True)

from .device import (DeviceSnapshot, make_mesh, make_mesh2,          # noqa: E402
                     mesh_lanes, mesh_parts, pin_snapshot)
from . import batch                                                  # noqa: E402  (defines the batch_* flags)
from .runtime import TpuRuntime                                      # noqa: E402
from . import traverse                                               # noqa: E402  (registers executor+rule)
from . import match_agg                                              # noqa: E402  (registers executor+rule)
from . import pipeline                                               # noqa: E402  (registers executor+rule; MUST follow match_agg — rule order)

__all__ = ["DeviceSnapshot", "make_mesh", "make_mesh2", "mesh_lanes",
           "mesh_parts", "pin_snapshot", "TpuRuntime"]
