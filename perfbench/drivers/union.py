"""Union sparse allreduce of embedding-gradient rows
(``SparseAllreduce.union_reduce``, the paper's mini-batch mode) on a mesh of
data-parallel workers.

Set-up draws a pool of distinct steps from the seed: per worker, the
distinct rows of its share of a batch whose ids follow a power law,
hashed and sorted, padded to the traffic's fixed capacity, with gradient
values made on the chips.  The traffic gives the law by the paper's
statistics: a degree exponent ``alpha`` (ids drawn as the endpoints of a
Chung-Lu graph of that exponent are) and ``node_fraction``, the share of
the index space a node holds, which sets how many ids a worker draws.
Set-up builds the allreduce and runs one call to compile.  The window calls
``union_reduce`` in a closed loop, one call at a time, cycling through the
pool.  The check compares, on every node, the outputs of calls sampled
from the seed with the union sum of the same inputs, exactly, and the
overflow count of every call.

The gradient values are multiples of 2^-20 in [-1, 1] (21 significant
bits, as the smoke run's): any sum of up to 4 of them is exact in float32,
so every correct merge order gives the reference's sum bit for bit, while
a value rounded to bfloat16 on the way (8 bits) does not.
"""
from __future__ import annotations

import time

import numpy as np

from perfbench import gen, reference
from perfbench.harness import Check

SENTINEL = gen.SENTINEL


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int, devices):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.devices = devices
        self.m = int(config["workers"])
        self.width = int(config["hidden_size"])
        self.cap = int(traffic["capacity"])
        self.out_cap = int(traffic["out_capacity"])
        self.limits = config["limits"]

    # -- set-up -------------------------------------------------------------
    def make_indices(self) -> np.ndarray:
        """``[steps, workers, capacity]`` hashed, sorted, SENTINEL-padded
        unique rows of each worker's tokens."""
        t, vocab = self.traffic, int(self.config["vocab_size"])
        exponent = 1.0 / (float(t["alpha"]) - 1.0)
        self.draws = gen.draws_for_fraction(vocab, exponent,
                                            float(t["node_fraction"]))
        rng = gen.rng_for(self.seed, 1)
        ranking = rng.permutation(vocab)
        mix = gen.MixHash.draw(rng)
        steps = int(t["pool_steps"])
        idx = np.full((steps, self.m, self.cap), SENTINEL, np.uint32)
        for s in range(steps):
            for w in range(self.m):
                ids = gen.zipf_ids(rng, self.draws, vocab, exponent, ranking)
                rows = np.sort(mix(np.unique(ids)))
                if len(rows) > self.cap:
                    raise ValueError(
                        f"step {s} worker {w}: {len(rows)} rows exceed the "
                        f"capacity {self.cap}")
                idx[s, w, : len(rows)] = rows
        return idx

    def make_values(self, idx_dev):
        """Gradient rows on the chips, one jitted call per step: multiples
        of 2^-20 in [-1, 1], zero in padding rows."""
        import jax
        import jax.numpy as jnp
        shard = idx_dev[0].sharding
        width = self.width

        def values(key, idx):
            k = jax.random.randint(key, idx.shape + (width,), -2 ** 20,
                                   2 ** 20 + 1, jnp.int32)
            v = k.astype(jnp.float32) * (2.0 ** -20)
            return jnp.where((idx != SENTINEL)[..., None], v, 0.0)

        values = jax.jit(values, out_shardings=shard)
        key = jax.random.key(gen.seed32(self.seed, 2))
        return [values(jax.random.fold_in(key, s), i)
                for s, i in enumerate(idx_dev)]

    def place_inputs(self):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_mesh
        self.mesh = make_mesh((self.m,), ("nodes",), devices=self.devices)
        self.idx = self.make_indices()
        shard = NamedSharding(self.mesh, P("nodes"))
        self.idx_dev = [jax.device_put(i, shard) for i in self.idx]
        self.val_dev = self.make_values(self.idx_dev)
        jax.block_until_ready(self.val_dev)

    def setup(self, spans: dict) -> None:
        import jax
        from repro.core import SparseAllreduce
        self.place_inputs()
        self.ar = SparseAllreduce(
            self.m, tuple(self.config["degrees"]), backend="device",
            mesh=self.mesh, merge=self.config["merge"],
            wire=self.config["wire"], plan_cache=False)
        jax.block_until_ready(self.ar.union_reduce(
            self.idx_dev[0], self.val_dev[0], self.out_cap))   # compiles
        own = (self.idx != SENTINEL).sum(axis=2)               # [steps, m]
        self.own = own
        self.union = np.array([len(np.unique(i[i != SENTINEL]))
                               for i in self.idx])

    def describe(self):
        yield (f"union: workers {self.m} draws per worker {self.draws} "
               f"degrees "
               f"{tuple(self.ar.plan.degrees)} merge {self.ar.merge} wire "
               f"{self.ar.wire} width {self.width} capacity C {self.cap} "
               f"out_capacity {self.out_cap} pool steps {len(self.idx)} "
               f"rows per worker {int(self.own.min())}..{int(self.own.max())} "
               f"union rows {int(self.union.min())}..{int(self.union.max())}")

    # -- window -------------------------------------------------------------
    def window(self, seconds: float, annotate) -> dict:
        import jax
        ar, steps = self.ar, len(self.idx_dev)
        misses0 = ar.union_plan_stats["misses"]
        rng = gen.rng_for(self.seed, 3)
        keep = int(self.traffic["checked_calls"])
        kept, ovfs, calls = [], [], 0
        t0 = time.perf_counter()
        deadline = t0 + seconds
        while True:
            s = calls % steps
            with annotate("bench.call"):
                out = ar.union_reduce(self.idx_dev[s], self.val_dev[s],
                                      self.out_cap)
            with annotate("bench.wait"):
                jax.block_until_ready(out)
            ovfs.append(out[2])
            # reservoir sample of the calls, drawn from the seed
            if calls < keep:
                kept.append((s, out))
            else:
                j = rng.randint(0, calls + 1)
                if j < keep:
                    kept[j] = (s, out)
            calls += 1
            if time.perf_counter() >= deadline:
                break
        window_s = time.perf_counter() - t0
        self.kept, self.ovfs = kept, ovfs
        self.misses = ar.union_plan_stats["misses"] - misses0
        return {"window_s": window_s, "attempted": calls, "calls": calls,
                "width": self.width, "own_rows": self.own.tolist(),
                "union_rows": self.union.tolist()}

    def release(self) -> None:
        import jax
        self.ovfs = np.asarray(jax.device_get(self.ovfs))
        del self.ar

    # -- check --------------------------------------------------------------
    def expected(self, s: int):
        """Host side of the reference for pool step ``s``: the sorted
        union of the workers' rows, SENTINEL-padded to ``out_capacity``,
        and each input row's position in it (``out_capacity`` for
        padding)."""
        idx = self.idx[s]
        valid = idx != SENTINEL
        union = np.unique(idx[valid])
        want = np.full(self.out_cap, SENTINEL, np.uint32)
        want[: len(union)] = union
        pos = np.where(valid, np.searchsorted(union, idx), self.out_cap)
        return want, pos.astype(np.int32), len(union)

    def compare_fn(self):
        """Jitted comparison on the chips: per node, the rows whose index
        differs from the union's, and the widest gap of a value to the
        union sum (rows past the union are not compared)."""
        import jax
        import jax.numpy as jnp
        out_cap = self.out_cap

        def compare(oi, ov, vals, pos, want, n_union):
            ref = reference.union_sum(vals, pos, out_cap)
            live = (jnp.arange(out_cap) < n_union)[None, :, None]
            gap = jnp.where(live, jnp.abs(ov - ref[None]), 0.0)
            rows = jnp.sum(oi != want[None], axis=1)
            return rows, jnp.max(gap, axis=(1, 2))

        return jax.jit(compare)

    def judge(self, outputs) -> list:
        """The comparison of ``outputs``, ``[(pool step, out_idx,
        out_val)]`` as ``union_reduce`` returns them, with the union sum of
        the same inputs; sets ``failed`` to the calls found wrong."""
        import jax
        compare = self.compare_fn()
        mismatch, gap, bad = 0, 0.0, 0
        for s, oi, ov in outputs:
            want, pos, n_union = self.expected(s)
            rows, gaps = jax.device_get(compare(oi, ov, self.val_dev[s], pos,
                                                want, n_union))
            mismatch += int(rows.sum())
            gap = max(gap, float(gaps.max()))
            bad += int(rows.sum() > 0 or gaps.max() > self.limits["max_gap"])
        self.failed = bad
        return [Check("max_gap", gap, float(self.limits["max_gap"])),
                Check("row_mismatch", mismatch, 0)]

    def check(self):
        checks = self.judge([(s, oi, ov) for s, (oi, ov, _) in self.kept])
        overflow = int(self.ovfs.sum())
        self.failed += int((self.ovfs.sum(axis=1) > 0).sum())
        return checks + [Check("overflow", overflow, 0),
                         Check("plan_misses", self.misses, 0)]

    def control(self, units: int) -> list:
        """The control, judged as the program is: on the first ``units``
        pool steps, the union sum with every input rounded to bfloat16 and
        summed in bfloat16 on the chips, in the program's place."""
        import jax
        import jax.numpy as jnp
        self.place_inputs()
        out_cap, shard = self.out_cap, self.val_dev[0].sharding

        def low(vals, pos, want):
            m, _, w = vals.shape
            total = reference.union_sum(vals, pos, out_cap, jnp.bfloat16)
            return (jnp.broadcast_to(want, (m, out_cap)),
                    jnp.broadcast_to(total, (m, out_cap, w)))

        low = jax.jit(low, out_shardings=(shard, shard))
        outputs = []
        for s in range(min(units, len(self.idx))):
            want, pos, _ = self.expected(s)
            outputs.append((s,) + low(self.val_dev[s], pos, want))
        return self.judge(outputs)
