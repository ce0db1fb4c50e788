"""Multi-chip parallelism: meshes, sharded stream ops, sharded training.

TPU-native replacement for the reference's distribution story (SURVEY §2.7): where the
reference spreads block tasks over cores and crosses hosts with ZMQ/TCP blocks, this layer
scales single logical operators over the ICI mesh — time-sharded streams with halo
exchange (sequence parallelism), channel-sharded filterbanks, and dp/fsdp-sharded model
training for the in-flowgraph ML path.
"""

from .mesh import (make_mesh, factor_devices, shard_params, virtual_cpu_mesh, P,
                   NamedSharding)
from .stream_sp import (sp_fir, sp_fir_fft_mag2, sp_fir_stream,
                        sp_fir_fft_mag2_stream, sp_channelizer, sp_channelizer_a2a,
                        sp_dechirp_scan)
from .pipeline_pp import make_pp_pipeline
from . import multihost

__all__ = ["make_mesh", "factor_devices", "shard_params", "virtual_cpu_mesh",
           "P", "NamedSharding",
           "sp_fir", "sp_fir_fft_mag2", "sp_fir_stream", "sp_fir_fft_mag2_stream",
           "sp_channelizer", "sp_channelizer_a2a", "sp_dechirp_scan",
           "make_pp_pipeline", "multihost"]
