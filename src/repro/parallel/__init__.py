"""Distribution: sharding rules, pipeline parallelism, sequence-parallel
kernel execution (shard_map halo exchange around the fused Pallas
kernels)."""
from .sharding import (param_shardings, batch_shardings, cache_shardings,
                       replicated, dp_axes, dp_size, tp_axis, tp_size)
from .pipeline import pipeline_apply
from .sp_attention import (sp_scope, sp_ctx, sp_band_attention,
                           sp_h1d_attention, sp_decode_attend,
                           sp_update_cache, sp_cache_specs,
                           sp_sharded_levels)
