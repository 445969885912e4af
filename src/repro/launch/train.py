"""Training driver.

Runs real steps on the available devices (CPU debug mesh or TPU pod) with
either the standard allreduce trainer or the paper's ADMM-consensus trainer
(``--trainer admm``).  Supports checkpoint/resume and the synthetic token
pipeline — the end-to-end example (examples/train_lm_consensus.py) drives a
~100M-param reduced config for a few hundred steps through this module.

Usage:
    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \
        --reduced --steps 200 --batch 8 --seq 256 --trainer admm
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import restore_latest, save_step
from repro.configs import get_config, get_reduced_config
from repro.configs.base import InputShape
from repro.core.consensus import ConsensusConfig
from repro.data.synthetic import token_batch
from repro.launch import mesh as mesh_lib
from repro.train import steps as steps_lib


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--trainer", default="allreduce",
                    choices=["allreduce", "admm"])
    ap.add_argument("--consensus-eta", type=float, default=0.05)
    ap.add_argument("--consensus-every", type=int, default=1)
    ap.add_argument("--mesh", default="",
                    help="'DxM' debug mesh (e.g. 2x2); empty = single device")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_reduced_config(args.arch) if args.reduced else get_config(args.arch)
    shape = InputShape("cli", args.seq, args.batch, "train")
    rng = jax.random.key(args.seed)

    mesh = None
    if args.mesh:
        d, m = (int(x) for x in args.mesh.split("x"))
        mesh = mesh_lib.make_debug_mesh(d, m)

    if args.trainer == "admm":
        if mesh is None:
            raise SystemExit("--trainer admm needs --mesh DxM (data axis = "
                             "consensus ring)")
        ccfg = ConsensusConfig(eta=args.consensus_eta,
                               every=args.consensus_every)
        state = steps_lib.make_consensus_train_state(cfg, rng, mesh, shape,
                                                     lr=args.lr)
        step_fn = steps_lib.make_consensus_train_step(cfg, mesh, ccfg,
                                                      lr=args.lr)
    else:
        state = steps_lib.make_train_state(cfg, rng, shape, lr=args.lr)
        step_fn = jax.jit(steps_lib.make_train_step(cfg, lr=args.lr),
                          donate_argnums=(0,))

    start = 0
    if args.ckpt_dir:
        s, restored = restore_latest(args.ckpt_dir)
        if restored is not None:
            # msgpack decodes NamedTuples as plain tuples; re-seat the
            # leaves into the live state's treedef (leaf order is preserved)
            state = jax.tree.unflatten(
                jax.tree.structure(state),
                [jnp.asarray(b, a.dtype) for a, b in
                 zip(jax.tree.leaves(state), jax.tree.leaves(restored))])
            start = s
            print(f"resumed from step {start}")

    ctx = contextlib.nullcontext() if mesh is None else jax.set_mesh(mesh)
    data_key = jax.random.key(args.seed + 1)
    t0 = time.time()
    with ctx:
        for step in range(start, args.steps):
            data_key, sub = jax.random.split(data_key)
            batch = token_batch(sub, cfg.vocab_size, args.batch, args.seq)
            state, metrics = step_fn(state, batch)
            if (step + 1) % args.log_every == 0 or step == start:
                m = {k: float(v) for k, v in metrics.items()}
                rate = (step + 1 - start) * args.batch * args.seq / \
                    (time.time() - t0)
                print(f"step {step+1:5d} " +
                      " ".join(f"{k}={v:.4f}" for k, v in m.items()) +
                      f" tok/s={rate:.0f}", flush=True)
            if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
                save_step(args.ckpt_dir, step + 1, jax.device_get(state))
    if args.ckpt_dir:
        save_step(args.ckpt_dir, args.steps, jax.device_get(state))
    print("done")
    return state


if __name__ == "__main__":
    main()
