"""The one import point for JAX symbols that have moved between releases.

The repo targets the one installed JAX (``jax>=0.9``), so these are plain
re-exports:

  * ``shard_map`` — ``jax.shard_map`` (replication check kwarg:
    ``check_vma``).
  * ``CompilerParams`` / ``PrefetchScalarGridSpec`` — the Pallas TPU
    compiler params and scalar-prefetch grid spec (the banded kernels'
    BlockSpec index maps read host-computed tables through it).

Every module under ``repro`` imports these names from here, never from
``jax`` directly (lint rules RA101/RA102 in ``repro.analysis.rules``), so a
future move is a one-file change.
"""
from __future__ import annotations

from jax import shard_map
from jax.experimental.pallas.tpu import CompilerParams, PrefetchScalarGridSpec

__all__ = ["shard_map", "CompilerParams", "PrefetchScalarGridSpec"]
