"""Execution context: device, mesh, seed, threads.

TPU-native analogue of ``xgboost::Context`` (reference ``include/xgboost/context.h:84``):
instead of {kCPU, kCUDA} + gpu_id, a context names a JAX platform and (for
distributed training) a ``jax.sharding.Mesh`` whose ``data`` axis carries the
row shard — the reference's ``DataSplitMode::kRow`` world — and whose optional
``feat`` axis is the column-split analogue.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Optional, Tuple

import jax
import numpy as np

from .params import Parameter, param_field

DATA_AXIS = "data"
FEATURE_AXIS = "feat"


@functools.lru_cache(maxsize=None)
def default_device(platform: Optional[str] = None):
    if platform is None or platform == "auto":
        return jax.devices()[0]
    return jax.devices(platform)[0]


@dataclass
class Context(Parameter):
    """Runtime context shared across the framework.

    ``device`` accepts 'auto' | 'cpu' | 'tpu' | 'gpu' (the reference accepts
    'cpu' | 'cuda:<ord>'; 'tpu' here plays the role 'cuda' does there).
    """

    device: str = param_field("auto", aliases=("device_type",))
    nthread: int = param_field(0, aliases=("n_jobs",))
    seed: int = param_field(0, aliases=("random_state",))
    seed_per_iteration: bool = param_field(False)
    verbosity: int = param_field(1)
    # mesh is not a serializable param; attached post-construction for distributed.
    _mesh: Any = field(default=None, repr=False, compare=False)

    def jax_device(self):
        return default_device(None if self.device == "auto" else self.device)

    @property
    def platform(self) -> str:
        return self.jax_device().platform

    def is_accelerator(self) -> bool:
        return self.platform not in ("cpu",)

    # --- mesh / distributed -------------------------------------------------
    @property
    def mesh(self) -> Optional[jax.sharding.Mesh]:
        return self._mesh

    def with_mesh(self, mesh: jax.sharding.Mesh) -> "Context":
        new = Context(device=self.device, nthread=self.nthread, seed=self.seed,
                      seed_per_iteration=self.seed_per_iteration,
                      verbosity=self.verbosity)
        new._mesh = mesh
        return new

    def data_axis_size(self) -> int:
        if self._mesh is None:
            return 1
        return self._mesh.shape.get(DATA_AXIS, 1)

    # --- rng ----------------------------------------------------------------
    def raw_seed(self, iteration: int = 0) -> np.uint32:
        """The uint32 key seed for ``iteration`` — the single source of
        truth shared by ``make_key`` and the fused round's in-jit
        derivation (they must never diverge: fused and general paths
        produce identical models by construction)."""
        seed = self.seed + iteration if self.seed_per_iteration else self.seed
        return np.uint32(seed & 0xFFFFFFFF)

    def make_key(self, iteration: int = 0) -> jax.Array:
        return jax.random.key(self.raw_seed(iteration))


def make_data_mesh(n_devices: Optional[int] = None,
                   devices: Optional[Tuple] = None) -> jax.sharding.Mesh:
    """A 1-D mesh over the ``data`` axis — the row-split (data-parallel) topology
    that the reference realises with rabit ranks (SURVEY.md §2.2)."""
    if devices is None:
        devices = jax.devices()
        if n_devices is not None:
            devices = devices[:n_devices]
    return jax.sharding.Mesh(np.array(devices), (DATA_AXIS,))
