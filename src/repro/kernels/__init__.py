"""The unified tile-sweep engine (Pallas TPU kernels + jnp oracles) for DPC's
two compute hot spots, and the pluggable backend registry that routes every
DPC hot path onto them."""
from .backend import (KernelBackend, available_backends,
                      default_backend_name, get_backend, register_backend,
                      rho_delta_sequential)
from .blocksparse import FlatWorklist, worklist_stats
from .ops import (dependent_masked, dependent_masked_gather, dependent_prefix,
                  fused_sweep, halo_density, halo_dependent, local_density,
                  local_density_delta, local_density_xy)
from .sweep import SweepSpec, tile_sweep

__all__ = ["local_density", "local_density_xy", "local_density_delta",
           "dependent_prefix", "dependent_masked", "dependent_masked_gather",
           "fused_sweep", "halo_density", "halo_dependent", "KernelBackend",
           "get_backend", "register_backend", "available_backends",
           "default_backend_name", "rho_delta_sequential", "SweepSpec",
           "tile_sweep", "FlatWorklist", "worklist_stats"]
