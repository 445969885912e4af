import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^^ MUST precede every jax-touching import: jax locks the device count on
# first init, and the production meshes need 512 placeholder host devices.

"""Multi-pod dry run (deliverable e).

For every (architecture x input-shape x mesh) combination this lowers and
compiles the real step function against ShapeDtypeStruct stand-ins — no
allocation — and records:

- memory_analysis()   : per-device argument/output/temp bytes (fits check)
- cost_analysis()     : per-device HLO FLOPs + bytes accessed
- collective bytes    : parsed from the post-SPMD HLO text, per opcode

Results append to a JSONL consumed by benchmarks/roofline.py and
EXPERIMENTS.md §Dry-run / §Roofline.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun \
        [--arch qwen2-0.5b] [--shape train_4k] [--mesh single,multi]
        [--mode allreduce|admm] [--out results/dryrun.jsonl]
"""
import argparse
import json
import re
import time
import traceback
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, SHAPES, get_config, shape_applicable
from repro.dist import sharding as shp
from repro.launch import mesh as mesh_lib
from repro.models import model as model_lib
from repro.train import steps as steps_lib

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1,
    "u64": 8, "u32": 4, "u16": 2, "u8": 1, "pred": 1,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


_BODY_RE = re.compile(r"body=%?([\w.\-]+)")
_COMP_RE = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{\s*$")


def collective_bytes(hlo_text: str, loop_multiplier: int = 1) -> Dict[str, Any]:
    """Sum operand bytes of every collective op in post-SPMD HLO, per op.

    Counts ``foo(...)`` and ``foo-start(...)`` forms; skips ``-done`` (the
    payload was counted at the start op).

    XLA prints a ``while`` body computation ONCE however many times it
    iterates, and every model here scans over its layers — so collectives
    found inside while-body computations are multiplied by
    ``loop_multiplier`` (= the scanned layer count, the dominant loop).
    This is first-order: inner flash/SSD scans carry no collectives.
    """
    body_names = set(_BODY_RE.findall(hlo_text))
    per_op = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    current_comp = ""
    for line in hlo_text.splitlines():
        m = _COMP_RE.match(line)
        if m and line.rstrip().endswith("{"):
            current_comp = m.group(1)
        mult = loop_multiplier if current_comp in body_names else 1
        for op in _COLLECTIVES:
            token_s = f" {op}-start("
            token = f" {op}("
            idx = line.find(token_s)
            if idx < 0:
                idx = line.find(token)
            if idx < 0:
                continue
            # operands: shapes inside the call parens
            args = line[idx:]
            shapes = _SHAPE_RE.findall(args[args.find("(") + 1:])
            if not shapes:  # fall back to the output shape (lhs)
                shapes = _SHAPE_RE.findall(line[:idx])
            per_op[op] += mult * sum(_shape_bytes(d, s) for d, s in shapes)
            counts[op] += mult
            break
    total = sum(per_op.values())
    return {"bytes_per_op": per_op, "counts": counts, "total_bytes": total,
            "loop_multiplier": loop_multiplier}


def _mem_dict(m) -> Dict[str, float]:
    if m is None:
        return {}
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        v = getattr(m, k, None)
        if v is not None:
            out[k] = float(v)
    return out


def build_lowering(arch: str, shape_name: str, mesh, mode: str = "allreduce"):
    """jit + in/out shardings + .lower() for one combination."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    data_specs = model_lib.input_specs(cfg, shape)
    long_mode = model_lib.use_long_mode(cfg, shape)

    def ns(spec_tree):
        return shp.named(mesh, spec_tree)

    if shape.step_kind == "train":
        if mode == "admm":
            state_shapes = steps_lib.consensus_state_specs(cfg, mesh, shape)
            axis = "data"
            st_spec = steps_lib.ConsensusTrainState(
                params=jax.tree.map(lambda _: P(axis), state_shapes.params),
                opt=jax.tree.map(lambda _: P(axis), state_shapes.opt),
                dual=jax.tree.map(lambda _: P(axis), state_shapes.dual),
                step=P())
            step = steps_lib.make_consensus_train_step(
                cfg, mesh, long_mode=long_mode)
            in_sh = (ns(st_spec), ns(shp.data_specs(
                data_specs, mesh, shape.global_batch)))
            lowered = jax.jit(
                step, in_shardings=in_sh, donate_argnums=(0,)
            ).lower(state_shapes, data_specs)
            return cfg, shape, lowered

        state_shapes = steps_lib.train_state_specs(cfg, shape)
        state_spec = shp.param_specs(state_shapes, mesh, shp.ctx_for(cfg))
        step = steps_lib.make_train_step(cfg, long_mode=long_mode)
        in_sh = (ns(state_spec),
                 ns(shp.data_specs(data_specs, mesh, shape.global_batch)))
        out_sh = (ns(state_spec), None)
        lowered = jax.jit(
            step, in_shardings=in_sh, out_shardings=out_sh,
            donate_argnums=(0,),
        ).lower(state_shapes, data_specs)
        return cfg, shape, lowered

    params_shapes = model_lib.param_specs(cfg, shape)
    param_spec = shp.param_specs(params_shapes, mesh, shp.ctx_for(cfg))

    if shape.step_kind == "prefill":
        step = steps_lib.make_prefill_step(cfg, long_mode=long_mode)
        in_sh = (ns(param_spec),
                 ns(shp.data_specs(data_specs, mesh, shape.global_batch)))
        lowered = jax.jit(step, in_shardings=in_sh).lower(
            params_shapes, data_specs)
        return cfg, shape, lowered

    # decode
    step = steps_lib.make_decode_step(cfg, long_mode=long_mode)
    cache_shapes = data_specs["cache"]
    cache_spec = shp.cache_specs(cache_shapes, mesh, shape.global_batch)
    tok_spec = shp.data_specs(
        {"tokens": data_specs["tokens"]}, mesh, shape.global_batch)["tokens"]
    in_sh = (ns(param_spec), ns(tok_spec), ns(cache_spec), None)
    out_sh = (None, ns(cache_spec), None)
    lowered = jax.jit(
        step, in_shardings=in_sh, out_shardings=out_sh, donate_argnums=(2,),
    ).lower(params_shapes, data_specs["tokens"], cache_shapes,
            data_specs["cache_index"])
    return cfg, shape, lowered


def run_one(arch: str, shape_name: str, multi_pod: bool,
            mode: str = "allreduce") -> Dict[str, Any]:
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": int(mesh.devices.size), "mode": mode,
    }
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        rec["status"] = "skipped"
        rec["reason"] = why
        return rec
    t0 = time.time()
    try:
        # set_mesh (not a bare `with mesh:`) so the abstract mesh is visible
        # during tracing — transformer.constrain_activations depends on it.
        with jax.set_mesh(mesh):
            cfg, shape, lowered = build_lowering(arch, shape_name, mesh, mode)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = _mem_dict(compiled.memory_analysis())
            cost = dict(compiled.cost_analysis() or {})
            hlo = compiled.as_text()
            n_scan = cfg.num_layers - (cfg.first_k_dense if cfg.is_moe else 0)
            coll = collective_bytes(hlo, loop_multiplier=max(n_scan, 1))
        from repro.launch import costs as costs_lib
        from repro.models import model as model_lib2
        ac = costs_lib.step_costs(
            cfg, shape, long_mode=model_lib2.use_long_mode(cfg, shape))
        rec.update({
            "status": "ok",
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "memory": mem,
            "flops": float(cost.get("flops", -1.0)),
            "bytes_accessed": float(cost.get("bytes accessed", -1.0)),
            "analytic": {"flops": ac.flops, "hbm_bytes": ac.hbm_bytes,
                         "param_state_bytes": ac.param_bytes_state,
                         "cache_bytes": ac.cache_bytes},
            "collectives": coll,
            "params": cfg.param_count(),
            "active_params": cfg.active_param_count(),
            "tokens": shape.global_batch * (shape.seq_len
                                            if shape.step_kind != "decode"
                                            else 1),
            "step_kind": shape.step_kind,
        })
    except Exception as e:  # a failure here is a bug in the system
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single,multi")
    ap.add_argument("--mode", default="allreduce",
                    choices=["allreduce", "admm"])
    ap.add_argument("--out", default="results/dryrun.jsonl")
    args = ap.parse_args()

    archs = ARCHS if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = args.mesh.split(",")

    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    n_fail = 0
    for arch in archs:
        for shape_name in shapes:
            for mesh_name in meshes:
                rec = run_one(arch, shape_name, mesh_name == "multi",
                              args.mode)
                with open(args.out, "a") as f:
                    f.write(json.dumps(rec) + "\n")
                status = rec["status"]
                extra = ""
                if status == "ok":
                    mem_gb = rec["memory"].get("temp_size_in_bytes", 0) / 2**30
                    extra = (f"flops/dev={rec['flops']:.3g} "
                             f"coll={rec['collectives']['total_bytes']/2**20:.1f}MiB "
                             f"temp={mem_gb:.2f}GiB "
                             f"compile={rec['compile_s']:.0f}s")
                elif status == "error":
                    n_fail += 1
                    extra = rec["error"][:200]
                else:
                    extra = rec["reason"]
                print(f"[{status:7s}] {arch:24s} {shape_name:12s} "
                      f"{rec['mesh']:8s} {extra}", flush=True)
    if n_fail:
        raise SystemExit(f"{n_fail} combinations failed")


if __name__ == "__main__":
    main()
