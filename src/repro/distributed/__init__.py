"""Multi-device substrates: meshes, sharded lookup, pipeline, fault.

Public surface: sharding rules re-exported below (`MeshAxes`,
`param_pspecs`, `batch_pspec`, `cache_pspecs`, `shard_params`), plus one
module per concern — `repro.distributed.sharded_lram` (model-parallel
LRAM lookup, quantization-aware), `pipeline` (GPipe over a mesh axis),
`collectives` (compressed psum), `context` (mesh-scoped activation
constraints), `fault` (heartbeat/straggler monitors).
"""

from repro.distributed.sharding import (  # noqa: F401
    MeshAxes,
    batch_pspec,
    cache_pspecs,
    param_pspecs,
    shard_params,
)
