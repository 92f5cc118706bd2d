"""PageRank through the device graph engine (``GraphEngine.run`` built by
``make_pagerank_engine``): LDBC Graphalytics settings, damping 0.85, a fixed
number of rounds per dispatch.

Set-up builds the graph of the traffic file (its ``law``, size and
``graph_seed``, ``perfbench.gen.graph``), relabels it by the run's seed (so every seed does the same work on an
isomorphic graph), builds the engine, places its tables on the chip and
runs one dispatch to compile.  The window dispatches again and again from
the uniform vector, feeding each dispatch's final state into the next,
with a few seconds of rounds in flight.
The check compares the state after every dispatch, and the last round's
scores, with the float64 reference run for as many rounds.
"""
from __future__ import annotations

import math
import time

import numpy as np

from perfbench import gen, reference, roofline
from perfbench.harness import Check

AHEAD_S = 4.0     # seconds of rounds kept in flight in the window
MAX_AHEAD = 8     # and never more dispatches than this


def vertices(ends: np.ndarray) -> np.ndarray:
    """The distinct vertices among edge ends, sorted: ``np.unique`` in
    one pass, where a sort of tens of millions of ends takes seconds."""
    return np.flatnonzero(np.bincount(ends))


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int, devices):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.devices = devices
        self.k = int(config["rounds_per_dispatch"])
        self.damping = float(config["damping"])
        self.limit = float(config["limits"]["max_rel_err"])

    # -- set-up -------------------------------------------------------------
    def make_edges(self) -> np.ndarray:
        return gen.relabel(gen.graph(self.traffic),
                           int(self.traffic["vertices"]),
                           gen.rng_for(self.seed, 1))

    def setup(self, spans: dict) -> None:
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.graph.pagerank import build_partitions, make_pagerank_engine
        from repro.launch.mesh import make_mesh
        self.n = int(self.traffic["vertices"])
        self.edges = self.make_edges()
        mesh = make_mesh((len(self.devices),), ("nodes",),
                         devices=self.devices)
        t0 = time.time()
        parts = build_partitions(self.edges, self.n, len(self.devices),
                                 seed=gen.seed32(self.seed, 2))
        engine, extras, p0 = make_pagerank_engine(
            parts, self.n, degrees=tuple(self.config["degrees"]),
            damping=self.damping, seed=gen.seed32(self.seed, 3), mesh=mesh)
        self.table_shape = tuple(extras["cols"].shape)
        shard = NamedSharding(engine.mesh, P(engine.axis))
        extras, p0 = jax.device_put((extras, p0), shard)
        jax.block_until_ready((extras, p0))
        spans["engine_build_s"] = time.time() - t0
        del parts
        self.engine, self.extras, self.p0 = engine, extras, p0
        warm, _, _ = engine.run(self.k, p0, extras)      # compiles
        warm.block_until_ready()
        self.needed_bytes = roofline.spmv_needed_bytes(self.edges)

    def describe(self):
        nnz = len(self.edges)
        rows = len(vertices(self.edges[:, 1]))
        m, r_cap, k = self.table_shape
        yield (f"pagerank: vertices {self.n} edges {nnz} partitions {m} "
               f"degrees {tuple(self.engine.ar.plan.degrees)} rows {rows} "
               f"table {r_cap}x{k} padding R*K/nnz {rows * k / nnz} "
               f"needed bytes/round {self.needed_bytes} "
               f"rounds/dispatch {self.k}")

    # -- window -------------------------------------------------------------
    def window(self, seconds: float, annotate) -> dict:
        """Dispatches back to back, each from the last one's state, with
        about ``AHEAD_S`` seconds of rounds in flight beyond the one waited
        on, so that a host stall does not leave the chip idle.  When the
        time is up nothing more is sent; the window closes once all that
        was sent has finished, and all of it counts."""
        engine, extras = self.engine, self.extras
        traces0 = engine.report["step_traces"]
        state, finals, last = self.p0, [], None
        ahead, waited = 1, 0
        t0 = time.perf_counter()
        deadline = t0 + seconds
        while True:
            with annotate("bench.dispatch"):
                state, last, _ = engine.run(self.k, state, extras)
            finals.append(state)
            if len(finals) - waited > ahead:
                with annotate("bench.wait"):
                    finals[waited].block_until_ready()
                waited += 1
                if waited == 1:         # the first dispatch's time sets it
                    first_s = time.perf_counter() - t0
                    ahead = min(MAX_AHEAD, max(1, math.ceil(AHEAD_S / first_s)))
            if time.perf_counter() >= deadline:
                break
        with annotate("bench.wait"):
            state.block_until_ready()
        window_s = time.perf_counter() - t0
        self.finals, self.last = finals, last
        self.retraces = engine.report["step_traces"] - traces0
        d = len(finals)
        return {"window_s": window_s, "attempted": d, "dispatches": d,
                "rounds": d * self.k, "edges": len(self.edges),
                "needed_bytes_per_round": self.needed_bytes,
                "reduce_least_bytes_per_round": 0}

    def release(self) -> None:
        import jax
        self.finals = np.asarray(jax.device_get(self.finals))[:, 0]
        self.last = np.asarray(jax.device_get(self.last))[0]
        self.in_idx = np.asarray(self.engine.in_sets[0], np.int64)
        self.out_idx = np.asarray(self.engine.out_sets[0], np.int64)
        del self.engine, self.extras, self.p0

    # -- check --------------------------------------------------------------
    def errors(self, states: np.ndarray, last_scores: np.ndarray,
               ref: np.ndarray) -> np.ndarray:
        """Per-dispatch max relative gap to the reference; the last
        dispatch also counts its final scores."""
        n_in = len(self.in_idx)
        r_in = ref[:, self.in_idx]
        err = np.max(np.abs(states[:, :n_in] - r_in) / r_in, axis=1)
        r_out = ref[-1, self.out_idx]
        err[-1] = max(err[-1], float(np.max(
            np.abs(last_scores - r_out) / r_out)))
        return err

    def scores_from_last(self) -> np.ndarray:
        q = self.last[: len(self.out_idx)].astype(np.float64)
        return (1 - self.damping) / self.n + self.damping * q

    def judge(self, states: np.ndarray, last_scores: np.ndarray) -> list:
        """The comparison of ``states``, the engine's state after each
        dispatch, and ``last_scores``, the last round's scores, with the
        float64 reference run for as many rounds; sets ``failed`` to the
        dispatches found wrong."""
        ref = reference.pagerank_states(self.edges, self.n,
                                        len(states) * self.k, self.k,
                                        self.damping)
        err = self.errors(states, last_scores, ref)
        self.failed = int(np.sum(err > self.limit))
        return [Check("max_rel_err", float(err.max()), self.limit)]

    def check(self):
        # the answers are read where the engine says it put them; they
        # must be the graph's sources and rows, in order
        layout = int(not (np.array_equal(self.in_idx,
                                          vertices(self.edges[:, 0]))
                          and np.array_equal(self.out_idx,
                                             vertices(self.edges[:, 1]))))
        checks = self.judge(self.finals.astype(np.float64),
                            self.scores_from_last())
        self.failed += layout
        return checks + [Check("layout_mismatch", layout, 0),
                         Check("retraces", self.retraces, 0)]

    def control(self, units: int) -> list:
        """The control, judged as the program is: ``units`` dispatches of
        the reference in bfloat16, in the program's place."""
        self.n = int(self.traffic["vertices"])
        self.edges = self.make_edges()
        self.in_idx = vertices(self.edges[:, 0])
        self.out_idx = vertices(self.edges[:, 1])
        low = reference.pagerank_states(self.edges, self.n, units * self.k,
                                        self.k, self.damping,
                                        dtype=reference.BF16)
        return self.judge(low[:, self.in_idx], low[-1, self.out_idx])
