"""Exact-resume soak harness: train / PageRank under fault schedules.

    PYTHONPATH=src python -m repro.launch.soak --job train --reduced \
        --steps 6 --ckpt-every 2 --faults rack --fault-at 3 \
        --num-failures 5 --rack-size 5 --out /tmp/soak

Runs a job to completion while a :mod:`repro.core.faults` schedule kills
devices mid-run, checkpointing every ``--ckpt-every`` steps through the
atomic :mod:`repro.checkpoint.store`.  ``--kill-at N`` hard-exits the
process (code 17) after step N — rerun with ``--resume`` to continue from
the newest valid checkpoint (corrupt ones are skipped) and finish with a
trajectory **step-identical** to an uninterrupted baseline: the batch
stream is replayed-and-skipped (``repro.launch.train.batch_stream``), the
checkpoint meta carries a ``train_fingerprint`` that must match, and
losses round-trip exactly through JSON.

Fault handling per step mirrors ``repro.resilience``:

  * dead devices that only hit spare capacity (or a redundant replica,
    ``--replication r``) are *absorbed* — the train step is rebuilt with
    the dead set masked via contribution weights, results unchanged;
  * a lost replica group with enough surviving pool devices triggers a
    *remap* — the same program re-bound to alive devices, bit-identical;
  * without spares the job *degrades* (drop to r=1 over survivors) or
    exits 3 on quorum loss.

The PageRank job drives :class:`repro.resilience.SupervisedEngineLoop`
over a power-law graph with the same checkpoint/kill/resume contract.
``benchmarks/bench_soak.py`` wraps this harness for the recovery-latency
and resume-overhead rows of BENCH_pr7.json; tier-1 runs it under a
subprocess kill-and-resume test (tests/test_resilience.py).
"""
from __future__ import annotations

import argparse
import sys

import numpy as np

from repro.checkpoint import store
from repro.core.faults import SCHEDULE_KINDS, make_schedule
from repro.resilience.events import (GROUP_LOST, QUORUM_LOST,
                                     REPLICA_ABSORBED, classify)

KILL_EXIT = 17      #: exit code of a --kill-at hard stop (not a failure)
QUORUM_EXIT = 3     #: exit code when too few devices survive


def parse_args(argv=None):
    """The soak CLI (flags shared by both jobs unless noted)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--job", default="train", choices=["train", "pagerank"])
    ap.add_argument("--steps", type=int, default=6,
                    help="total train steps / PageRank rounds")
    ap.add_argument("--ckpt-every", type=int, default=2,
                    help="checkpoint (and scan-block) interval; keep it "
                         "fixed between a baseline and a resumed run to "
                         "compare trajectories bit-for-bit")
    ap.add_argument("--faults", default="none",
                    choices=("none",) + SCHEDULE_KINDS,
                    help="failure schedule kind over the device pool "
                         "(repro.core.faults; 'cascade' accumulates and "
                         "never heals)")
    ap.add_argument("--fault-at", type=int, default=0,
                    help="first step/round at which the schedule applies")
    ap.add_argument("--num-failures", type=int, default=1)
    ap.add_argument("--rack-size", type=int, default=4)
    ap.add_argument("--kill-at", type=int, default=0,
                    help="hard-exit (code 17) after this step completes "
                         "and checkpoints — simulates a crash; ignored "
                         "under --resume")
    ap.add_argument("--resume", action="store_true",
                    help="continue from the newest valid checkpoint in "
                         "--out (corrupt checkpoints are skipped; the "
                         "stored fingerprint must match this invocation)")
    ap.add_argument("--out", required=True,
                    help="checkpoint + final-state directory")
    ap.add_argument("--seed", type=int, default=0)
    # train job
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--sync", default="ring",
                    choices=["ring", "hier", "sparse"])
    ap.add_argument("--merge", default="sort",
                    choices=["sort", "fused", "banded"])
    ap.add_argument("--dp", type=int, default=4,
                    help="logical data-parallel shards (train job)")
    ap.add_argument("--replication", type=int, default=1,
                    help="r-way replica groups over dp*r device roles")
    # pagerank job
    ap.add_argument("--vertices", type=int, default=400)
    ap.add_argument("--edges", type=int, default=2000)
    ap.add_argument("--graph-nodes", type=int, default=4,
                    help="graph partitions M (PageRank job)")
    return ap.parse_args(argv)


def _latest_valid(out_dir: str):
    """Newest loadable checkpoint ``(step, arrays, meta)`` or ``None``,
    skipping corrupt artifacts (the atomic-save + CheckpointError
    contract makes 'corrupt' detectable instead of garbage)."""
    for step, base in store.list_checkpoints(out_dir):
        try:
            arrays, meta = store.load_flat(base)
            return step, arrays, meta
        except store.CheckpointError as e:
            print(f"skipping corrupt checkpoint {base}: {e}",
                  file=sys.stderr)
    return None


# ---------------------------------------------------------------------------
# train job
# ---------------------------------------------------------------------------

def run_train(args) -> int:
    import jax
    import jax.numpy as jnp
    from jax.tree_util import tree_map

    from repro.configs import get_config
    from repro.launch.mesh import make_mesh
    from repro.launch.train import batch_stream
    from repro.models import transformer as T
    from repro.optim.adamw import AdamW, AdamWState
    from repro.train.step import make_train_step, mesh_ctx, train_fingerprint

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    pool = jax.devices()
    dp, r = args.dp, args.replication
    m_roles = dp * r
    if len(pool) < m_roles:
        raise ValueError(f"{len(pool)} devices < {m_roles} roles")
    schedule = None
    if args.faults != "none":
        schedule = make_schedule(args.faults, len(pool), args.num_failures,
                                 seed=args.seed, rack_size=args.rack_size)
    fp = train_fingerprint(cfg, batch=args.batch, seq=args.seq, lr=args.lr,
                           sync=args.sync, merge=args.merge, dp=dp,
                           replication=r, seed=args.seed)

    # role -> pool position; sticky until a fault forces a remap/shrink
    assignment = list(range(m_roles))
    r_eff = r
    step_cache = {}

    def get_step(assign, dead_roles, r_now):
        key = (tuple(assign), frozenset(dead_roles), r_now)
        hit = step_cache.get(key)
        if hit is None:
            mesh = make_mesh((len(assign), 1), ("data", "model"),
                             devices=[pool[p] for p in assign])
            fn, _ = make_train_step(
                cfg, mesh, sync=args.sync, opt=AdamW(lr=args.lr),
                dp_degrees=None, sync_merge=args.merge,
                sparse_tokens_hint=max(8, args.batch * args.seq
                                       // len(assign)),
                replication=r_now, dead=set(dead_roles) or None)
            hit = step_cache[key] = (fn, mesh)
        return hit

    mesh0 = get_step(assignment, frozenset(), r_eff)[1]
    params = T.init_params(cfg, mesh_ctx(mesh0).tp, seed=args.seed)
    opt = AdamW(lr=args.lr)
    opt_state = opt.init(params)
    start, losses, events = 0, [], []
    if args.resume:
        hit = _latest_valid(args.out)
        if hit is not None:
            start, arrays, meta = hit
            if meta["fingerprint"] != fp:
                raise SystemExit(
                    f"checkpoint fingerprint {meta['fingerprint']} does not "
                    f"match this invocation ({fp}) — resuming would diverge")
            like = {"params": params, "opt_m": opt_state.m,
                    "opt_v": opt_state.v}
            tree = store.load(f"{args.out}/ckpt-{start}", like)
            params = tree["params"]
            opt_state = AdamWState(
                step=jnp.asarray(arrays["opt_step"]),
                m=tree["opt_m"], v=tree["opt_v"])
            losses = [float(x) for x in meta["losses"]]
            events = list(meta.get("events", []))
            print(f"resumed at step {start} from {args.out}/ckpt-{start}")

    stream = batch_stream(cfg, args.batch, args.seq, seed=args.seed)
    for _ in range(start):
        next(stream)       # exact resume: replay-and-skip the batch source

    def checkpoint(step_no):
        store.save(f"{args.out}/ckpt-{step_no}",
                   {"params": tree_map(np.asarray, params),
                    "opt_m": tree_map(np.asarray, opt_state.m),
                    "opt_v": tree_map(np.asarray, opt_state.v),
                    "opt_step": np.asarray(opt_state.step)},
                   meta={"step": step_no, "losses": losses,
                         "fingerprint": fp, "events": events})

    dead_roles = frozenset()
    for i in range(start, args.steps):
        dead_pool = set(schedule.dead_at(i)) \
            if schedule is not None and i >= args.fault_at else set()
        new_dead = frozenset(role for role, p in enumerate(assignment)
                             if p in dead_pool)
        ev = classify(len(assignment), r_eff, set(new_dead))
        if ev.klass == GROUP_LOST or \
                (ev.klass == QUORUM_LOST and r_eff > 1):
            alive = [p for p in range(len(pool)) if p not in dead_pool]
            if len(alive) >= len(assignment):
                # remap: same program on alive devices — bit-identical
                assignment = alive[: len(assignment)]
                new_dead = frozenset()
                events.append(f"remap@{i}")
            elif len(alive) >= dp:
                # degrade: drop replication, keep every logical shard
                assignment, r_eff = alive[:dp], 1
                new_dead = frozenset()
                events.append(f"drop-replication@{i}")
            else:
                print(f"QUORUM_LOST step {i}: {len(alive)} alive < dp={dp}")
                return QUORUM_EXIT
            # state buffers live on dead devices; re-host before re-binding
            params = tree_map(np.asarray, params)
            opt_state = tree_map(np.asarray, opt_state)
        elif ev.klass == QUORUM_LOST:
            print(f"QUORUM_LOST step {i}: dead roles {sorted(new_dead)}")
            return QUORUM_EXIT
        elif ev.klass == REPLICA_ABSORBED and new_dead != dead_roles:
            events.append(f"absorbed@{i}")
        dead_roles = new_dead

        step_fn, _ = get_step(assignment, dead_roles, r_eff)
        b = next(stream)
        batch = {k: jnp.asarray(v) for k, v in b.items()}
        if r_eff > 1:
            batch = {k: jnp.tile(v, (r_eff,) + (1,) * (v.ndim - 1))
                     for k, v in batch.items()}
        params, opt_state, m = step_fn(params, opt_state, batch)
        losses.append(float(m["loss"]))
        done = i + 1
        if args.ckpt_every and done % args.ckpt_every == 0:
            checkpoint(done)
        if args.kill_at and done == args.kill_at and not args.resume:
            print(f"KILL step {done} (simulated crash)")
            sys.stdout.flush()
            return KILL_EXIT

    store.save(f"{args.out}/final",
               {"params": tree_map(np.asarray, params),
                "opt_m": tree_map(np.asarray, opt_state.m),
                "opt_v": tree_map(np.asarray, opt_state.v),
                "opt_step": np.asarray(opt_state.step)},
               meta={"steps": args.steps, "losses": losses,
                     "fingerprint": fp, "events": events})
    print(f"SOAK_OK job=train steps={args.steps} "
          f"loss={losses[-1]:.6f} events={events}")
    return 0


# ---------------------------------------------------------------------------
# pagerank job
# ---------------------------------------------------------------------------

def run_pagerank(args) -> int:
    import jax

    from repro.data.pipeline import powerlaw_graph
    from repro.graph.pagerank import (assemble_pagerank_scores,
                                      build_partitions, make_pagerank_app,
                                      pagerank_state)
    from repro.resilience.engine import SupervisedEngineLoop

    pool = jax.devices()
    m = args.graph_nodes
    damping = 0.85
    edges = powerlaw_graph(args.vertices, args.edges, seed=args.seed)
    parts = build_partitions(edges, args.vertices, m, seed=args.seed)
    app, out_sets, in_sets = make_pagerank_app(parts, args.vertices, damping)
    schedule = None
    if args.faults != "none":
        schedule = make_schedule(args.faults, len(pool), args.num_failures,
                                 seed=args.seed, rack_size=args.rack_size)

    killed = {"flag": False}

    def on_block(rnd, state):
        if args.kill_at and rnd >= args.kill_at and not args.resume \
                and not killed["flag"]:
            killed["flag"] = True
            print(f"KILL round {rnd} (simulated crash)")
            sys.stdout.flush()
            sys.exit(KILL_EXIT)

    loop = SupervisedEngineLoop(
        out_sets, in_sets, app, degrees=(m,), seed=args.seed,
        schedule=schedule, fault_at=args.fault_at, ckpt_dir=args.out,
        ckpt_every=args.ckpt_every, pool=pool, on_block=on_block)
    extras, p0 = pagerank_state(parts, args.vertices,
                                loop.engine.u_cap, loop.engine.uin_cap)
    start, state = 0, p0
    if args.resume:
        hit = _latest_valid(args.out)
        if hit is not None:
            start, arrays, meta = hit
            state = arrays["state"]
            print(f"resumed at round {start} from {args.out}/ckpt-{start}")

    state, last_q = loop.run(args.steps, state, extras, start_round=start)
    scores = assemble_pagerank_scores(parts, np.asarray(last_q),
                                      args.vertices, damping)
    store.save(f"{args.out}/final",
               {"state": np.asarray(state), "last_q": np.asarray(last_q),
                "scores": scores},
               meta={"rounds": args.steps, "remaps": loop.remaps,
                     "events": [e.klass for e in loop.events]})
    print(f"SOAK_OK job=pagerank rounds={args.steps} remaps={loop.remaps} "
          f"events={[e.klass for e in loop.events]}")
    return 0


def main(argv=None) -> int:
    """Entry point; returns the process exit code (0 ok, 17 simulated
    crash, 3 quorum lost)."""
    args = parse_args(argv)
    rc = run_train(args) if args.job == "train" else run_pagerank(args)
    return rc


if __name__ == "__main__":
    sys.exit(main())
