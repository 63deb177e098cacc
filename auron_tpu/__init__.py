"""auron_tpu — a TPU-native columnar query-execution framework.

A brand-new framework with the capabilities of Apache Auron (incubating)
(reference: /root/reference): it accepts a fully-optimized physical plan
(e.g. serialized from a Spark-like front-end) as a plan IR, and executes it
as columnar programs over device-resident batches — but where Auron lowers
to a Rust DataFusion/SIMD engine on CPU (native-engine/), this framework
lowers to jax.jit-compiled XLA programs on TPU:

- operators are jitted columnar kernels over fixed-capacity padded batches
  (static shapes => one XLA compilation per schema x capacity bucket);
- repartitioning rides ICI all-to-all collectives via jax.shard_map over a
  jax.sharding.Mesh (auron_tpu.parallel) instead of shuffle files;
- an HBM-budgeted memory manager with host-offload spill
  (auron_tpu.memmgr) replaces Auron's auron-memmgr wait-or-spill stack;
- a C++ host runtime (auron_tpu.native) provides compressed batch serde,
  spill/shuffle file IO and hashing where Auron uses Rust.

64-bit types are enabled globally: SQL semantics require int64 sums,
timestamp micros and 64-bit hashes (Spark's BIGINT / xxhash64) — jax's
x64 switch is all-or-nothing, and without it BIGINT columns silently
truncate.  The cost is contained instead (the round-1 x64 audit): every
index/permutation/iota/mask path is explicit int32 (capacities are
< 2^31 by construction), murmur3 runs in uint32, and only column VALUES
whose SQL type demands it carry 64-bit lanes.
"""

from __future__ import annotations

import jax

jax.config.update("jax_enable_x64", True)

__version__ = "0.1.0"

from auron_tpu.config import conf  # noqa: E402
from auron_tpu.ir.schema import (  # noqa: E402
    DataType,
    Field,
    Schema,
    TypeId,
)

__all__ = [
    "DataType",
    "Field",
    "Schema",
    "TypeId",
    "conf",
    "__version__",
]
