import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""§Perf hillclimb driver: hypothesis -> change -> re-lower -> measure.

Runs named optimization variants for the three chosen cells, records the
roofline terms per variant, and emits the iteration log consumed by
EXPERIMENTS.md §Perf.  Variants compose config overrides (fused attention,
remat policy, microbatching) and logical mesh remaps (same 256 chips,
different axis split).

Usage:
  PYTHONPATH=src python -m repro.launch.hillclimb --cell musicgen \
      [--out experiments/perf]
  PYTHONPATH=src python -m repro.launch.hillclimb --all
"""
import argparse
import json
import time

import jax

from ..configs import get_config
from ..models.config import SHAPES, TrainConfig
from ..train import step as TS
from ..models import transformer as T
from . import dryrun as DR
from . import jaxpr_cost as JC
from . import roofline as RL
from .mesh import make_production_mesh
from .sharding import (batch_specs, cache_specs, param_specs, state_specs,
                       to_shardings)

import jax.numpy as jnp


def _mesh_for(remesh: str | None):
    if not remesh:
        return make_production_mesh(), "pod16x16"
    d, m = remesh.split("x")
    mesh = jax.make_mesh((int(d), int(m)), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    return mesh, f"remap{remesh}"


def run_variant(arch: str, shape_name: str, variant: str, *,
                overrides: dict | None = None, remesh: str | None = None,
                microbatches: int | None = None,
                hypothesis: str = "") -> dict:
    cfg = get_config(arch)
    if overrides:
        cfg = cfg.replace(**overrides)
    shape = SHAPES[shape_name]
    mesh, mesh_name = _mesh_for(remesh)
    key = jax.random.PRNGKey(0)
    t0 = time.time()

    if shape.kind == "train":
        n_micro = microbatches if microbatches is not None \
            else DR.MICROBATCHES.get(arch, 1)
        tc = TrainConfig(n_microbatches=n_micro)
        state_shape = jax.eval_shape(
            lambda k: TS.init_state(k, cfg, tc), key)
        batch_shape = DR.input_specs(cfg, shape)
        fn = TS.build_train_step(cfg, tc)
        args = (state_shape, batch_shape)
        with jax.set_mesh(mesh):
            jitted = jax.jit(fn, in_shardings=(
                to_shardings(state_specs(cfg, state_shape, mesh), mesh),
                to_shardings(batch_specs(batch_shape, mesh), mesh)))
            lowered = jitted.lower(*args)
    elif shape.kind == "prefill":
        params_shape = jax.eval_shape(lambda k: T.init_params(k, cfg), key)
        batch_shape = DR.input_specs(cfg, shape)
        fn = DR._prefill_step_fn(cfg)
        args = (params_shape, batch_shape)
        with jax.set_mesh(mesh):
            jitted = jax.jit(fn, in_shardings=(
                to_shardings(param_specs(cfg, params_shape, mesh), mesh),
                to_shardings(batch_specs(batch_shape, mesh), mesh)))
            lowered = jitted.lower(*args)
    else:
        params_shape = jax.eval_shape(lambda k: T.init_params(k, cfg), key)
        batch_shape = DR.input_specs(cfg, shape)
        cache_shape = jax.eval_shape(
            lambda: T.init_caches(cfg, shape.global_batch, shape.seq_len,
                                  dtype=jnp.bfloat16))
        fn = DR._decode_step_fn(cfg)
        args = (params_shape, cache_shape, batch_shape)
        with jax.set_mesh(mesh):
            jitted = jax.jit(fn, in_shardings=(
                to_shardings(param_specs(cfg, params_shape, mesh), mesh),
                to_shardings(cache_specs(cfg, cache_shape, mesh), mesh),
                to_shardings(batch_specs(batch_shape, mesh), mesh)))
            lowered = jitted.lower(*args)

    compiled = lowered.compile()
    jc = JC.jaxpr_cost(fn, *args)
    record = {
        "arch": arch, "shape": shape_name, "variant": variant,
        "mesh": mesh_name, "hypothesis": hypothesis,
        "jaxpr_cost": {k: v for k, v in jc.items()
                       if not isinstance(v, dict)},
        "top_byte_ops": jc["top_byte_ops"],
        "collectives": RL.collective_bytes(compiled),
        "memory": RL.memory_dict(compiled.memory_analysis()),
        "compile_s": round(time.time() - t0, 1),
    }
    record["jaxpr_cost"]["flops"] = jc["flops"]
    record["jaxpr_cost"]["bytes_major"] = jc["bytes_major"]
    record["roofline"] = RL.roofline_terms(
        {"jaxpr_cost": jc, "collectives": record["collectives"]},
        cfg, shape, mesh.size)
    r = record["roofline"]
    print(f"[{arch} x {shape_name}] {variant:28s} "
          f"compute {r['compute_s']:.4f}  memory {r['memory_s']:.4f}  "
          f"coll {r['collective_s']:.4f}  -> bound {r['bound_s']:.4f} "
          f"({r['dominant']}), roofline {r['roofline_fraction']:.3f}")
    return record


CELLS = {
    "musicgen": ("musicgen-medium", "train_4k", [
        ("baseline", {}, dict()),
        ("fused_attention",
         dict(overrides={"fused_attention": True}),
         dict(hypothesis="88% of memory bytes are flash score/softmax "
              "spills (jaxpr top_byte_ops); fusing attention keeps them in "
              "VMEM -> memory term drops ~5x; collective term unaffected")),
        ("fused+remesh_d32m8",
         dict(overrides={"fused_attention": True}, remesh="32x8"),
         dict(hypothesis="24 heads do not divide TP=16 -> GSPMD replicates "
              "attention activations and all-gathers qkv every layer "
              "(2.5GB fwd / 7.5GB bwd per layer iter = the 9.7s collective "
              "bound). TP=8 divides 24 -> pure head-parallel attention, "
              "no all-gathers; per-device AR bytes also halve via dp=32 -> "
              "collective term -90%+")),
        ("fused+remesh+dots_remat",
         dict(overrides={"fused_attention": True, "remat": "block_dots"},
              remesh="32x8"),
         dict(hypothesis="block remat recomputes every dot in the refwd "
              "(~1.33x dot flops); saving dot outputs removes recompute -> "
              "compute term -15-25%")),
    ]),
    "mamba2": ("mamba2-780m", "prefill_32k", [
        ("baseline", {}, dict()),
        ("remesh_d32m8",
         dict(remesh="32x8"),
         dict(hypothesis="collective term = 48 per-layer TP all-reduces + "
              "B/C all-gathers of (B/dp, S, *) activations; halving TP "
              "(16->8) and doubling DP halves per-device collective bytes "
              "-> collective term -50%, compute unchanged")),
        ("remesh_d64m4",
         dict(remesh="64x4"),
         dict(hypothesis="push further: TP=4 quarters collective bytes; "
              "B=32 < dp=64 leaves batch under-sharded -> expect "
              "divisibility fallback; check net effect")),
    ]),
    "qwen2moe": ("qwen2-moe-a2.7b", "train_4k", [
        ("baseline", {}, dict()),
        ("fused_attention",
         dict(overrides={"fused_attention": True}),
         dict(hypothesis="~72% of memory bytes are attention intermediates "
              "-> fuse; MoE dispatch gather/scatter (8.7e12 B) remains")),
        ("fused+dots_remat",
         dict(overrides={"fused_attention": True, "remat": "block_dots"}),
         dict(hypothesis="remove expert-matmul recompute in refwd")),
        ("fused+dots+cap1.0",
         dict(overrides={"fused_attention": True, "remat": "block_dots",
                         "capacity_factor": 1.0}),
         dict(hypothesis="capacity 1.25->1.0 cuts expert compute+bytes 20% "
              "at the cost of more dropped tokens (quality trade, "
              "documented)")),
    ]),
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", choices=list(CELLS) + ["all"], default="all")
    ap.add_argument("--out", default="experiments/perf")
    args = ap.parse_args()
    cells = list(CELLS) if args.cell == "all" else [args.cell]
    os.makedirs(args.out, exist_ok=True)
    for cell in cells:
        arch, shape, variants = CELLS[cell]
        records = []
        for vname, kw, meta in variants:
            rec = run_variant(arch, shape, vname, **kw, **meta)
            records.append(rec)
        with open(os.path.join(args.out, f"{cell}.json"), "w") as f:
            json.dump(records, f, indent=1)
    print("[hillclimb] done")


if __name__ == "__main__":
    main()
