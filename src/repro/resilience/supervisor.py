"""Supervised sparse allreduce: faults in, degraded-but-correct results out.

:class:`ResilientAllreduce` wraps the device-backend
:class:`repro.core.api.SparseAllreduce` with the supervision loop the
paper's target systems (PowerGraph, Hadoop) run under churn:

  1. **Detect & classify** — before every dispatch the supervisor reads
     the active dead set (a ``probe`` callable, a
     :class:`repro.core.faults.FailureSchedule`, or a static set) and
     classifies it (:func:`repro.resilience.events.classify`); a
     ``DeadLogicalNode`` escaping the wrapped reduce is caught and
     re-classified the same way, so both detection paths agree.
  2. **Retry with bounded exponential backoff** — a *group-lost* event may
     be transient (network partition, restarting host), so the supervisor
     re-probes up to ``max_retries`` times, sleeping
     ``backoff_s * backoff_mult**attempt`` between probes
     (:func:`retry_until_alive`, host-testable with an injected clock).
  3. **Degrade per policy** — if the group stays lost:
     ``mode="shrink"`` replans over the surviving logical shards (keeping
     replication when enough devices survive), ``mode="drop_replication"``
     shrinks to r=1, ``mode="fail"`` re-raises.  Survivor results are
     bit-identical to a fresh fault-free reduce over the same surviving
     set — verified exhaustively in ``tests/test_resilience.py``.

Replans are cheap by construction: *replica-absorbed* events are a
weights-only repair (``SparseAllreduce.reconfig_dead`` — no host
replanning), and survivor replans key into the autotuner's plan cache and
in-process memo via ``shrunk_from`` (:mod:`repro.core.autotune`), so a
repeat shrink to the same survivor set reuses both the frozen plan and
the compiled reduce.  ``benchmarks/bench_soak.py`` measures all three
recovery tiers.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from repro.core.api import SparseAllreduce
from repro.core.faults import FailureSchedule
from repro.core.netmodel import EC2_2013, Fabric
from repro.core.replication import DeadLogicalNode
from repro.launch.mesh import make_mesh
from .events import (GROUP_LOST, NO_FAULT, QUORUM_LOST, REPLICA_ABSORBED,
                     FaultEvent, QuorumLost, classify)

#: Degraded-mode policies, in decreasing willingness to continue.
POLICY_MODES = ("shrink", "drop_replication", "fail")


@dataclasses.dataclass(frozen=True)
class DegradedPolicy:
    """What the supervisor does when a replica group stays dead.

    ``mode``: ``"shrink"`` (replan over survivors, keep replication when
    the surviving device count allows), ``"drop_replication"`` (replan
    over survivors at r=1 — maximum surviving capacity, no further fault
    tolerance), ``"fail"`` (re-raise ``DeadLogicalNode`` after retries —
    for jobs where partial results are worthless).  Retries and quorum
    apply to every mode.
    """

    mode: str = "shrink"
    max_retries: int = 3
    backoff_s: float = 0.05
    backoff_mult: float = 2.0
    quorum_frac: float = 0.5

    def __post_init__(self):
        if self.mode not in POLICY_MODES:
            raise ValueError(
                f"mode must be one of {POLICY_MODES}, got {self.mode!r}")
        if self.max_retries < 0:
            raise ValueError(f"max_retries must be >= 0, got "
                             f"{self.max_retries}")
        if self.backoff_s < 0:
            raise ValueError(f"backoff_s must be >= 0, got {self.backoff_s}")
        if self.backoff_mult < 1.0:
            raise ValueError(
                f"backoff_mult must be >= 1, got {self.backoff_mult}")
        if not 0.0 < self.quorum_frac <= 1.0:
            raise ValueError(
                f"quorum_frac must be in (0, 1], got {self.quorum_frac}")


def retry_until_alive(dead_at: Callable[[int], Optional[Set[int]]],
                      policy: DegradedPolicy, m_physical: int,
                      replication: int, *,
                      step: int = 0,
                      sleep: Callable[[float], None] = time.sleep
                      ) -> Tuple[FaultEvent, List[FaultEvent]]:
    """Probe ``dead_at(attempt)`` until the fault clears or retries run out.

    Sleeps ``backoff_s * backoff_mult**attempt`` between probes (injected
    ``sleep`` makes this host-testable without wall-clock waits).  Returns
    ``(final_event, all_events)`` — the final event is the first
    non-*group-lost* classification, or the last *group-lost* one after
    ``max_retries`` extra probes; the caller applies the policy mode.
    """
    events: List[FaultEvent] = []
    for attempt in range(policy.max_retries + 1):
        ev = classify(m_physical, replication, dead_at(attempt),
                      quorum_frac=policy.quorum_frac,
                      step=step, attempt=attempt)
        events.append(ev)
        if ev.klass != GROUP_LOST:
            return ev, events
        if attempt < policy.max_retries:
            sleep(policy.backoff_s * policy.backoff_mult ** attempt)
    return events[-1], events


@dataclasses.dataclass
class ReduceOutcome:
    """One supervised reduce: per-*original*-logical-shard results plus
    provenance.  ``values[i]`` exists for every shard that survived
    (all of them when ``degraded`` is False); lost shards are absent —
    their contributions died with their replica group.  ``shrink`` is the
    :attr:`ResilientAllreduce.last_shrink` record when a replan happened.
    """

    values: Dict[int, np.ndarray]
    event: FaultEvent
    degraded: bool
    attempts: int
    shrink: Optional[dict] = None


class ResilientAllreduce:
    """Supervised two-call sparse allreduce (module docstring).

    Same ``config``/``reduce`` shape as :class:`SparseAllreduce`
    (device backend), plus a fault source: a ``schedule``
    (:class:`FailureSchedule`, consulted at ``dead_at(step)``), a
    ``probe`` callable ``(step, attempt) -> dead set`` (overrides the
    schedule — retries re-probe, so transient faults can heal), or a
    static ``dead`` set.  ``reduce``/``union_reduce`` return
    :class:`ReduceOutcome` — results keyed by original logical shard id.
    """

    def __init__(self, num_nodes: int, degrees="auto", *,
                 replication: int = 1,
                 schedule: Optional[FailureSchedule] = None,
                 probe: Optional[Callable] = None,
                 dead: Optional[Set[int]] = None,
                 policy: Optional[DegradedPolicy] = None,
                 sleep: Callable[[float], None] = time.sleep,
                 mesh=None, seed: int = 0, value_width: int = 1,
                 merge: str = "sort", fabric: Fabric = EC2_2013,
                 expected_nnz: float = 1e5, index_range: float = 1e6,
                 plan_cache=True, retune: bool = False):
        import jax
        self.policy = policy or DegradedPolicy()
        self.schedule = schedule
        self.probe = probe
        self.static_dead = set(dead or ())
        self.sleep = sleep
        self.num_nodes = num_nodes
        self.replication = replication
        self.seed = seed
        self.merge = merge
        self.fabric = fabric
        self.expected_nnz = expected_nnz
        self.index_range = index_range
        m_phys = num_nodes * replication
        if mesh is None:
            devs = jax.devices()
            if len(devs) < m_phys:
                raise ValueError(
                    f"{len(devs)} devices < {m_phys} physical nodes")
            mesh = make_mesh((m_phys,), ("nodes",), devices=devs)
        self.mesh = mesh
        # The base instance is always fault-free at config time; dead sets
        # are applied per-reduce via reconfig_dead (incremental repair).
        self.base = SparseAllreduce(
            num_nodes, degrees, backend="device", replication=replication,
            dead=None, fabric=fabric, seed=seed, value_width=value_width,
            mesh=mesh, expected_nnz=expected_nnz, index_range=index_range,
            merge=merge, plan_cache=plan_cache, retune=retune)
        self._out_indices = self._in_indices = None
        self._shrunk: Dict[Tuple[Tuple[int, ...], int], SparseAllreduce] = {}
        self.last_shrink: Optional[dict] = None
        self.events: List[FaultEvent] = []
        self.stats = {"reduces": 0, "absorbed": 0, "repairs": 0,
                      "retries": 0, "shrinks": 0, "shrink_reuses": 0,
                      "quorum_lost": 0}

    @property
    def num_physical(self) -> int:
        """Physical device count of the un-degraded fleet."""
        return self.num_nodes * self.replication

    # ------------------------------------------------------------------
    def config(self, out_indices: Sequence[np.ndarray],
               in_indices: Sequence[np.ndarray]):
        """The paper's ``config``: freeze routing for the fault-free fleet
        and keep the logical index lists for survivor replans."""
        self._out_indices = [np.asarray(o, np.uint32) for o in out_indices]
        self._in_indices = [np.asarray(i, np.uint32) for i in in_indices]
        return self.base.config(self._out_indices, self._in_indices)

    # ------------------------------------------------------------------
    def _dead_at(self, step: int, attempt: int) -> Set[int]:
        if self.probe is not None:
            return set(self.probe(step, attempt) or ())
        if self.schedule is not None:
            return set(self.schedule.dead_at(step))
        return set(self.static_dead)

    def _supervise(self, step: int) -> FaultEvent:
        """Run detection + retry/backoff; raise :class:`QuorumLost` or
        (mode="fail") ``DeadLogicalNode`` on unrecoverable events."""
        ev, evs = retry_until_alive(
            lambda a: self._dead_at(step, a), self.policy,
            self.num_physical, self.replication, step=step,
            sleep=self.sleep)
        self.events.extend(evs)
        self.stats["retries"] += len(evs) - 1
        if ev.klass == QUORUM_LOST:
            self.stats["quorum_lost"] += 1
            raise QuorumLost(
                f"step {step}: only {len(ev.survivors)} of "
                f"{self.num_nodes} logical shards survive "
                f"(quorum_frac={self.policy.quorum_frac}, "
                f"dead={sorted(ev.dead)})")
        if ev.klass == GROUP_LOST and self.policy.mode == "fail":
            raise DeadLogicalNode(
                f"step {step}: replica groups {list(ev.lost)} lost after "
                f"{self.policy.max_retries} retries and policy is "
                f"mode='fail' (dead={sorted(ev.dead)})")
        return ev

    # ------------------------------------------------------------------
    def _shrink_for(self, ev: FaultEvent) -> Tuple[SparseAllreduce,
                                                   Tuple[int, ...]]:
        """The survivor instance for ``ev`` (cached per survivor set)."""
        survivors = ev.survivors
        m2 = len(survivors)
        alive = [i for i in range(self.num_physical) if i not in ev.dead]
        if self.policy.mode == "drop_replication":
            r2 = 1
        else:
            r2 = self.replication if m2 * self.replication <= len(alive) \
                else 1
        key = (survivors, r2)
        hit = self._shrunk.get(key)
        if hit is not None:
            self.stats["shrink_reuses"] += 1
            self.last_shrink = hit[1]
            return hit[0], survivors
        degrees, source = self._survivor_degrees(m2, r2)
        pool = list(self.mesh.devices.flat)
        mesh2 = make_mesh((m2 * r2,), ("nodes",),
                          devices=[pool[i] for i in alive[: m2 * r2]])
        ar2 = SparseAllreduce(
            m2, degrees, backend="device", replication=r2, dead=None,
            fabric=self.fabric, seed=self.seed, value_width=self.base.width,
            mesh=mesh2, expected_nnz=self.expected_nnz,
            index_range=self.index_range, merge=self.merge,
            plan_cache=self.base.plan_cache or False)
        if self._out_indices is not None:
            ar2.config([self._out_indices[i] for i in survivors],
                       [self._in_indices[i] for i in survivors])
        record = {"survivors": survivors, "degrees": tuple(degrees),
                  "replication": r2, "degrees_source": source,
                  "config_cache": ar2.config_cache}
        self._shrunk[key] = (ar2, record)
        self.last_shrink = record
        self.stats["shrinks"] += 1
        return ar2, survivors

    def _survivor_degrees(self, m2: int, r2: int):
        if m2 == 1:
            return (), "trivial"
        if self.base.degrees_source == "explicit" or \
                self.base.plan_cache is None:
            from repro.core.topology import tune
            return tune(m2, n0=self.expected_nnz,
                        total_range=self.index_range,
                        fabric=self.fabric).degrees, "tuned"
        from repro.core.autotune import resolve_degrees
        return resolve_degrees(
            m2, n0=self.expected_nnz, total_range=self.index_range,
            fabric=self.fabric, merge=self.merge, replication=r2,
            width=self.base.width, cache=self.base.plan_cache,
            shrunk_from=self.num_nodes)

    # ------------------------------------------------------------------
    def reduce(self, out_values: Sequence[np.ndarray],
               step: int = 0) -> ReduceOutcome:
        """Supervised planned reduce at ``step`` (module docstring)."""
        self.stats["reduces"] += 1
        ev = self._supervise(step)
        attempts = ev.attempt
        if ev.klass in (NO_FAULT, REPLICA_ABSORBED):
            try:
                if set(ev.dead) != set(self.base.dead or ()):
                    self.base.reconfig_dead(set(ev.dead) or None)
                    self.stats["repairs"] += 1
                if ev.klass == REPLICA_ABSORBED:
                    self.stats["absorbed"] += 1
                vals = self.base.reduce(out_values)
                return ReduceOutcome(
                    values=dict(enumerate(vals)), event=ev,
                    degraded=False, attempts=attempts)
            except DeadLogicalNode:
                # Fault raced past the probe: fall through to degraded.
                ev = dataclasses.replace(ev, klass=GROUP_LOST)
                if self.policy.mode == "fail":
                    raise
        ar2, survivors = self._shrink_for(ev)
        vals2 = ar2.reduce([out_values[i] for i in survivors])
        return ReduceOutcome(
            values={sid: vals2[k] for k, sid in enumerate(survivors)},
            event=ev, degraded=True, attempts=attempts,
            shrink=self.last_shrink)

    # ------------------------------------------------------------------
    def union_reduce(self, idx, val, out_capacity: int, step: int = 0,
                     use_kernel: bool = False) -> ReduceOutcome:
        """Supervised dynamic-index union reduce at ``step``.

        ``outcome.values[i]`` is the ``(idx, val, overflow)`` triple for
        surviving logical node ``i`` (full fleet when not degraded).
        """
        self.stats["reduces"] += 1
        ev = self._supervise(step)
        attempts = ev.attempt
        if ev.klass in (NO_FAULT, REPLICA_ABSORBED):
            try:
                if set(ev.dead) != set(self.base.dead or ()):
                    # union fns key (and bake) the dead set themselves;
                    # no planned-path repair needed when un-configured.
                    if self.base._planned is not None:
                        self.base.reconfig_dead(set(ev.dead) or None)
                    else:
                        self.base.dead = set(ev.dead) or None
                    self.stats["repairs"] += 1
                if ev.klass == REPLICA_ABSORBED:
                    self.stats["absorbed"] += 1
                oi, ov, ovf = self.base.union_reduce(
                    idx, val, out_capacity, use_kernel=use_kernel)
                values = {i: (np.asarray(oi[i]), np.asarray(ov[i]),
                              np.asarray(ovf[i]))
                          for i in range(self.num_nodes)}
                return ReduceOutcome(values=values, event=ev,
                                     degraded=False, attempts=attempts)
            except DeadLogicalNode:
                ev = dataclasses.replace(ev, klass=GROUP_LOST)
                if self.policy.mode == "fail":
                    raise
        ar2, survivors = self._shrink_for(ev)
        idx = np.asarray(idx)
        val = np.asarray(val)
        oi, ov, ovf = ar2.union_reduce(
            idx[list(survivors)], val[list(survivors)], out_capacity,
            use_kernel=use_kernel)
        values = {sid: (np.asarray(oi[k]), np.asarray(ov[k]),
                        np.asarray(ovf[k]))
                  for k, sid in enumerate(survivors)}
        return ReduceOutcome(values=values, event=ev, degraded=True,
                             attempts=attempts, shrink=self.last_shrink)
