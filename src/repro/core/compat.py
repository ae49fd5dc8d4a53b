"""The JAX spellings the core algorithms share, for the one installed JAX.

The repository supports exactly the JAX that ``requirements.txt`` pins
(jax 0.9.0, with libtpu for the chip).  ``shard_map`` is
``jax.shard_map`` — callers pass ``check_vma=False`` where a Pallas call
sits in the body — and ``vmem_scratch`` is ``pltpu.VMEM``, the fused
kernels' accumulator allocation.  Both are plain aliases: there is no
version probe and no fallback.
"""
from __future__ import annotations

import jax
from jax.experimental.pallas import tpu as _pltpu

shard_map = jax.shard_map
vmem_scratch = _pltpu.VMEM

__all__ = ["shard_map", "vmem_scratch"]
