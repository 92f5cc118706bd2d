"""Supervised graph-engine runs: faults + checkpoints around ``GraphEngine``.

:class:`SupervisedEngineLoop` chops an iterative run (PageRank / HADI /
spectral) into blocks, and between blocks does the three supervisor moves:

  1. **Consult the fault schedule** over a device *pool* larger than the
     engine's mesh (the spare-capacity model: an M-partition job on an
     N-device fleet, N >= M).  Dead pool devices that host no engine
     partition are *replica-absorbed*-style no-ops.
  2. **Remap on device loss** — when an engine device dies but >= M pool
     devices survive, :meth:`repro.graph.engine.GraphEngine.remesh`
     rebinds the identical program to the first M alive devices.  The
     partition, resolved degrees, and seed are unchanged, so the continued
     trajectory is **bit-identical** to an uninterrupted run — the
     engine-side analogue of the paper's §V "any replica can stand in"
     guarantee, with spare devices playing the replicas.
  3. **Checkpoint + exact resume** — after every block the state pytree is
     saved through the atomic :func:`repro.checkpoint.store.save`;
     :meth:`run` accepts ``start_round`` to continue a reloaded state.
     Blocks are the ``lax.scan`` unit, so a resumed run re-executes the
     same block structure and reproduces the baseline trajectory exactly
     (asserted by ``tests/test_resilience.py`` and the soak harness).

Without spare capacity the loop degrades per ``repartition`` (a caller
callback building a smaller job) or fails fast with :class:`QuorumLost`.
"""
from __future__ import annotations

from typing import Any, Callable, List, Optional, Sequence, Set, Tuple

import numpy as np

from repro.core.faults import FailureSchedule
from repro.core.netmodel import EC2_2013, Fabric
from repro.graph.engine import EngineApp, GraphEngine
from .events import (GROUP_LOST, REPLICA_ABSORBED, FaultEvent, QuorumLost)


class SupervisedEngineLoop:
    """Blocked, supervised, checkpointed ``GraphEngine`` run (module
    docstring).

    ``pool``: the physical device fleet (default ``jax.devices()``); the
    engine runs on the first ``M = len(out_sets)`` of it and remaps within
    it on failures.  ``schedule.dead_at(round)`` (gated by ``fault_at``)
    gives the dead *pool positions* per round.  ``ckpt_every`` is both the
    checkpoint interval and the scan block length — keep it fixed between
    a baseline and a faulted/resumed run to compare trajectories
    bit-for-bit.  ``on_block(round, state)`` runs after each completed
    block (the soak harness's kill hook).
    """

    def __init__(self, out_sets, in_sets, app: EngineApp, *,
                 degrees="auto", seed: int = 0, fabric: Fabric = EC2_2013,
                 schedule: Optional[FailureSchedule] = None,
                 fault_at: int = 0,
                 repartition: Optional[Callable] = None,
                 ckpt_dir: Optional[str] = None, ckpt_every: int = 0,
                 plan_cache=True, pool=None,
                 on_block: Optional[Callable] = None):
        import jax
        self.pool = list(pool) if pool is not None else list(jax.devices())
        m = len(out_sets)
        if len(self.pool) < m:
            raise ValueError(
                f"pool of {len(self.pool)} devices < {m} partitions")
        self.schedule = schedule
        self.fault_at = fault_at
        self.repartition = repartition
        self.ckpt_dir = ckpt_dir
        self.ckpt_every = ckpt_every
        self.on_block = on_block
        self.assignment = list(range(m))   # partition -> pool position
        self._dead: Set[int] = set()
        self.events: List[FaultEvent] = []
        self.remaps = 0
        self.engine = GraphEngine(
            out_sets, in_sets, app, degrees=degrees,
            mesh=self._mesh(), seed=seed, fabric=fabric,
            plan_cache=plan_cache)

    def _mesh(self):
        from repro.launch.mesh import make_mesh
        return make_mesh((len(self.assignment),), ("nodes",),
                         devices=[self.pool[p] for p in self.assignment])

    # ------------------------------------------------------------------
    def _supervise(self, rnd: int) -> None:
        """Apply the dead set active at round ``rnd`` (remap or raise)."""
        if self.schedule is None or rnd < self.fault_at:
            return
        dead = set(self.schedule.dead_at(rnd))
        if dead == self._dead:
            return
        self._dead = dead
        m = len(self.assignment)
        hit = [i for i, p in enumerate(self.assignment) if p in dead]
        alive = [p for p in range(len(self.pool)) if p not in dead]
        if not hit:
            # spares died; the engine's devices are untouched
            self.events.append(FaultEvent(
                step=rnd, attempt=0, dead=frozenset(dead),
                klass=REPLICA_ABSORBED, lost=(),
                survivors=tuple(range(m))))
            return
        if len(alive) >= m:
            self.assignment = alive[:m]
            self.engine = self.engine.remesh(self._mesh())
            self.remaps += 1
            self.events.append(FaultEvent(
                step=rnd, attempt=0, dead=frozenset(dead),
                klass=GROUP_LOST, lost=tuple(hit),
                survivors=tuple(range(m))))
            return
        if self.repartition is not None:
            self.engine, self.assignment = self.repartition(self, alive)
            self.remaps += 1
            self.events.append(FaultEvent(
                step=rnd, attempt=0, dead=frozenset(dead),
                klass=GROUP_LOST, lost=tuple(hit),
                survivors=tuple(range(len(self.assignment)))))
            return
        raise QuorumLost(
            f"round {rnd}: {len(alive)} alive pool devices cannot host "
            f"{m} partitions and no repartition callback is set "
            f"(dead={sorted(dead)})")

    # ------------------------------------------------------------------
    def run(self, rounds: int, state, extras=None, *,
            start_round: int = 0) -> Tuple[Any, Any]:
        """Run ``rounds`` total rounds, continuing at ``start_round``
        (0 for a fresh run; a resumed caller passes the checkpointed
        round).  Returns ``(final_state, last_out)``; intermediate states
        land in ``ckpt_dir`` as ``ckpt-<round>`` artifacts.
        """
        from jax.tree_util import tree_map
        block = self.ckpt_every if self.ckpt_every > 0 else rounds
        rnd = start_round
        last_out = None
        while rnd < rounds:
            before = self.engine
            self._supervise(rnd)
            if self.engine is not before:
                # re-host the state: the new mesh places blocks on the
                # surviving devices, so hand numpy to the next dispatch
                state = tree_map(np.asarray, state)
            k = min(block, rounds - rnd)
            state, last_out, _ = self.engine.run(k, state, extras)
            rnd += k
            if self.ckpt_dir:
                from repro.checkpoint import store
                store.save(f"{self.ckpt_dir}/ckpt-{rnd}",
                           {"state": tree_map(np.asarray, state)},
                           meta={"round": rnd,
                                 "events": [e.klass for e in self.events]})
            if self.on_block is not None:
                self.on_block(rnd, state)
        return state, last_out
