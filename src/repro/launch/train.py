"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
        --reduced --steps 50 --batch 8 --seq 256 --sync sparse

Builds a mesh over the available devices (data x model), streams synthetic
Zipf batches (repro.data), runs the shard_map train step with the selected
gradient-sync mode (ring | hier | sparse — the paper's primitive), logs
loss/throughput, and checkpoints.

For the paper's *iterative graph* workloads (PageRank / HADI / spectral)
the entry point is the device-resident engine instead:
``repro.graph.engine`` (used by ``repro.graph.pagerank`` et al. with
``backend="device"``) fuses k SpMV+reduce rounds into one dispatch.

``--dp-degrees auto`` goes through the calibrated autotuner with its
persistent plan cache (``repro.core.autotune``; cache at
``$REPRO_PLAN_CACHE`` or ``~/.cache/repro/plans``, ``--retune`` to force
a fresh sweep) — the full workflow is documented in TUNING.md.
"""
from __future__ import annotations

import argparse
import time
from typing import List

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint.store import save as ckpt_save
from repro.configs import ARCHS, get_config
from repro.data.pipeline import Batcher
from repro.launch.cache import use_compile_cache
from repro.launch.mesh import make_mesh
from repro.optim.adamw import AdamW, AdamWState
from repro.train.step import _ns, init_params_sharded, make_train_step


def batch_stream(cfg, batch: int, seq: int, seed: int = 0):
    """The launcher's deterministic synthetic batch source, as a reusable
    generator of numpy batch dicts (tokens / labels + per-arch extras).

    Exactly the sequence :func:`main` consumes: a seeded Zipf
    :class:`Batcher` plus one sequential ``RandomState(seed)`` for the
    multimodal tensors — so two streams with equal ``(cfg, batch, seq,
    seed)`` are byte-identical, and *exact resume* is "recreate the stream
    and skip the first k batches" (``repro.launch.soak`` relies on this
    for step-identical resumed trajectories)."""
    batcher = iter(Batcher(vocab=cfg.vocab, batch=batch, seq=seq, seed=seed))
    rng = np.random.RandomState(seed)
    while True:
        toks, labels = next(batcher)
        b = {"tokens": toks, "labels": labels}
        if cfg.img_tokens:
            b["img_embeds"] = rng.randn(
                batch, cfg.img_tokens, cfg.d_model).astype(np.float32)
        if cfg.enc_layers:
            b["enc_frames"] = rng.randn(
                batch, cfg.enc_seq, cfg.d_model).astype(np.float32)
        yield b


def init_sharded_state(cfg, mesh, seed: int = 0):
    """Parameters and AdamW state created in place on the mesh, sharded as
    the train step expects them (nothing is staged on device 0 first)."""
    params = init_params_sharded(cfg, mesh, seed=seed)
    shard = jax.tree.map(lambda x: x.sharding, params)
    opt_shard = AdamWState(step=NamedSharding(mesh, P()), m=shard, v=shard)
    opt_state = jax.jit(AdamW().init, out_shardings=opt_shard)(params)
    return params, opt_state


def main(argv=None) -> float:
    """Run the launcher; returns the final step's loss."""
    return train(argv)[-1]


def train(argv=None) -> List[float]:
    """Run the launcher; returns every step's loss."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b", choices=list(ARCHS))
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale variant of the arch (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--sync", default="ring", choices=["ring", "hier", "sparse"])
    ap.add_argument("--dp-degrees", default="auto",
                    help="butterfly degree sequence for the data axis, e.g. "
                         "'4,4'; 'auto' (default) resolves through the "
                         "calibrated autotuner (repro.core.autotune, built "
                         "on repro.core.topology.tune): the fabric is the "
                         "stored calibration for this backend when one "
                         "exists (else the nominal TPU fabric per axis) and "
                         "the chosen degrees are cached persistently in "
                         "$REPRO_PLAN_CACHE (default ~/.cache/repro/plans), "
                         "so repeat launches skip the sweep — see TUNING.md; "
                         "'rr' keeps one round-robin (degree = axis size) "
                         "stage per axis")
    ap.add_argument("--retune", action="store_true",
                    help="bypass the persistent plan cache for this launch: "
                         "re-run the degree sweep and overwrite the cached "
                         "plan (use after recalibrating the fabric or "
                         "changing the workload shape)")
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--merge", default="sort",
                    choices=["sort", "fused", "banded"],
                    help="per-butterfly-layer merge for sparse sync: full "
                         "re-sort, the fused Pallas rank-merge pipeline, or "
                         "its band-limited (near-linear tile work) variant")
    ap.add_argument("--wire", default="raw",
                    choices=["raw", "delta", "delta+bf16", "delta+int8ef"],
                    help="on-wire payload encoding for sparse sync "
                         "(repro.kernels.wirecodec): 'delta' bit-packs the "
                         "sorted index stream (bit-identical results); "
                         "'delta+bf16' / 'delta+int8ef' additionally "
                         "quantize values, the latter with an error-"
                         "feedback carry re-injected each step; requires "
                         "--sync sparse for non-raw values")
    ap.add_argument("--sync-overlap", default="off",
                    choices=["off", "bucketed"],
                    help="gradient-sync schedule (hier/sparse sync only): "
                         "'bucketed' splits the dense butterfly leaves into "
                         "byte-bounded buckets issued stage-major, so sync "
                         "collectives interleave with compute instead of "
                         "forming one monolithic chain; results are bitwise "
                         "identical to 'off' (tests/test_overlap.py)")
    ap.add_argument("--sync-bucket-kb", type=int, default=4096,
                    help="bucket byte budget (KiB) for --sync-overlap "
                         "bucketed; leaves above the budget get a bucket "
                         "of their own")
    ap.add_argument("--replication", type=int, default=1,
                    help="r-way replicated data parallelism (paper SV fault "
                         "tolerance): the data axis hosts dp/r logical batch "
                         "shards, each fed to r devices; gradient sync takes "
                         "each shard from its first alive replica")
    ap.add_argument("--dead", default="",
                    help="comma-separated dead data-slot ids to mask "
                         "(simulated failures; survivable iff every replica "
                         "group keeps an alive member, else DeadLogicalNode)")
    ap.add_argument("--data-axis", type=int, default=0,
                    help="data-parallel size (0 = all devices)")
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--untied", action="store_true",
                    help="untie embeddings (sparse sync acts on input emb)")
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.untied:
        import dataclasses
        cfg = dataclasses.replace(cfg, tie_embeddings=False)

    ndev = len(jax.devices())
    dsize = args.data_axis or (ndev // args.model_axis)
    mesh = make_mesh((dsize, args.model_axis), ("data", "model"))
    dead = {int(x) for x in args.dead.split(",") if x} or None
    repl = ""
    if args.replication > 1 or dead:
        repl = (f" replication={args.replication}"
                f" dead={sorted(dead) if dead else []}")
    print(f"mesh data={dsize} model={args.model_axis}; arch={cfg.name} "
          f"({cfg.param_count()/1e6:.1f}M params) sync={args.sync}{repl}")

    if args.dp_degrees in ("rr", ""):
        dp_degrees = None                      # round-robin per axis
    elif args.dp_degrees == "auto":
        dp_degrees = "auto"
    else:
        degs = tuple(int(x) for x in args.dp_degrees.split(","))
        dp_degrees = {"data": degs}
    step, specs = make_train_step(cfg, mesh, sync=args.sync,
                                opt=AdamW(lr=args.lr),
                                microbatch=args.microbatch,
                                dp_degrees=dp_degrees,
                                sparse_tokens_hint=max(
                                    8, args.batch * args.seq // dsize),
                                sync_merge=args.merge, sync_wire=args.wire,
                                replication=args.replication, dead=dead,
                                retune=args.retune,
                                sync_overlap=args.sync_overlap,
                                sync_bucket_bytes=args.sync_bucket_kb * 1024)
    params, opt_state = init_sharded_state(cfg, mesh, seed=args.seed)
    batch_shard = _ns(mesh, specs["batch"])
    stream = batch_stream(cfg, args.batch, args.seq, seed=args.seed)

    t_start = time.time()
    r = args.replication
    losses = []
    for i in range(args.steps):
        batch = next(stream)
        if r > 1:
            # mirror the logical batch onto every replica slab: device
            # i + j*(data/r) sees logical shard i's rows for all j
            batch = {k: np.tile(v, (r,) + (1,) * (v.ndim - 1))
                     for k, v in batch.items()}
        # host -> each device's own rows, no detour through device 0
        batch = {k: jax.device_put(v, batch_shard[k])
                 for k, v in batch.items()}
        params, opt_state, m = step(params, opt_state, batch)
        losses.append(m["loss"])
        if i % 10 == 0 or i == args.steps - 1:
            dt = time.time() - t_start
            tput = (i + 1) * args.batch * args.seq / dt
            print(f"step {i:5d} loss {float(m['loss']):.4f} "
                  f"gnorm {float(m['gnorm']):.3f} aux {float(m['aux']):.4f} "
                  f"tok/s {tput:.0f}")
    if args.ckpt:
        ckpt_save(args.ckpt, {"params": params},
                  meta={"arch": cfg.name, "steps": args.steps})
        print(f"checkpoint -> {args.ckpt}")
    return [float(x) for x in losses]


if __name__ == "__main__":
    main()
