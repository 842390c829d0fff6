import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

DOC = """Multi-pod dry-run: lower + compile every (architecture x shape x mesh)
cell with abstract inputs, and extract the roofline terms.

The lines above MUST run before any jax import: jax locks the device
count and platform at first initialization.  The dry-run lowers for 512
virtual host devices, so it is pinned to the CPU: launched next to a
process that holds the chip, it must not try to take the chip too.  Do not
set these flags anywhere else (tests and benchmarks see one device).

Per cell this produces (and appends to --out, default
``benchmarks/artifacts/dryrun_<mesh>.json``):
  * memory_analysis  -> bytes per device (proves the cell fits HBM)
  * cost_analysis    -> HLO FLOPs / bytes for the roofline compute/memory terms
  * collective bytes -> parsed from the post-SPMD optimized HLO, summed per
    collective kind (all-gather / all-reduce / reduce-scatter / all-to-all /
    collective-permute) for the roofline collective term.

Usage:
  python -m repro.launch.dryrun --arch mamba2-370m --shape train_4k
  python -m repro.launch.dryrun --all --mesh single --out artifacts/d.json
  python -m repro.launch.dryrun --all --mesh multi          # 2-pod, 512 chips
"""
__doc__ = DOC

import argparse
import dataclasses
import json
import pathlib
import re
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import SHAPES, cell_is_skipped, get_config, grid, input_specs
from repro.launch import steps as step_lib
from repro.launch.mesh import make_production_mesh
from repro.models import build_model
from repro.optim.adamw import adamw
from repro.parallel import sharding as shd
from repro.parallel.constraints import ActivationPolicy, activation_sharding

COLLECTIVE_RE = re.compile(
    r"=\s+([a-z0-9]+)\[([0-9,]*)\][^ ]*\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
)

DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}


def collective_bytes(hlo_text: str) -> dict:
    """Sum result-shape bytes of every collective op in optimized HLO."""
    out = {"all-gather": 0, "all-reduce": 0, "reduce-scatter": 0,
           "all-to-all": 0, "collective-permute": 0}
    counts = dict.fromkeys(out, 0)
    for m in COLLECTIVE_RE.finditer(hlo_text):
        dtype, dims, kind = m.groups()
        if dtype not in DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out[kind] += n * DTYPE_BYTES[dtype]
        counts[kind] += 1
    return {"bytes": out, "counts": counts,
            "total_bytes": int(sum(out.values()))}


def _spec_tree_to_shardings(mesh, tree):
    return shd.named_tree(mesh, tree)


def run_cell(arch: str, shape_name: str, mesh, mesh_name: str,
             use_flash: bool = False, extra_overrides: Optional[dict] = None):
    """Lower+compile one cell; return the roofline record."""
    shape = SHAPES[shape_name]
    overrides = dict(extra_overrides or {})
    grad_accum = overrides.pop("_grad_accum", "outside")
    seq_shard = overrides.pop("_seq_shard", False)
    moe_flat = overrides.pop("_moe_flat", False)
    kv_seq = overrides.pop("_kv_seq", False)
    zero3 = overrides.pop("_zero3", False)
    decode_tp = overrides.pop("_decode_tp", False)
    cfg = get_config(arch, **overrides)
    if moe_flat and cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, dispatch="flat"))
    rules = shd.make_rules(cfg, mesh)
    if kv_seq:
        rules = dataclasses.replace(rules, kv_heads_shard=False)
    mesh_size = int(np.prod(list(mesh.shape.values())))
    want_zero3 = zero3 or (shape.kind == "train"
                           and cfg.train_parallelism == "zero3")
    if want_zero3 and shape.global_batch % mesh_size == 0:
        # pure ZeRO-3: batch + weights sharded over the flattened mesh, no TP
        axes = tuple(a for a in mesh.axis_names)
        rules = dataclasses.replace(
            rules, batch=axes, fsdp=axes, tensor=None, expert_parallel=False)
    elif want_zero3:
        # zero3 requires global_batch %% mesh devices == 0 (one sequence per
        # device minimum); fall back to 2D FSDP+TP with microbatching
        cfg = dataclasses.replace(cfg, train_microbatches=16)
    if shape.kind == "train" and cfg.train_microbatches > 1:
        # each microbatch must still shard over the batch axes:
        # (B / M) %% prod(batch axes) == 0  ->  M | B / batch_axes
        import math
        bax = int(np.prod([mesh.shape[a] for a in rules.batch])) or 1
        m_max = max(1, shape.global_batch // bax)
        m = math.gcd(cfg.train_microbatches, m_max)
        if m != cfg.train_microbatches:
            cfg = dataclasses.replace(cfg, train_microbatches=m)
    cache_rules = rules
    if shape.kind == "decode" and not kv_seq:
        if decode_tp or cfg.param_count() * 2 <= 12e9:
            # small models: weights TP-resident, no per-step FSDP gather
            rules = dataclasses.replace(rules, fsdp=None)
            cache_rules = rules
        else:
            # large models: weights stay 256-way sharded; decode activations
            # are replicated (KB-scale) so matmuls emit tiny partial-sum ARs
            # instead of gathering GBs of weights.  The cache keeps its
            # batch sharding (attention contracts per batch row locally).
            rules = dataclasses.replace(rules, batch=())
            cache_rules = dataclasses.replace(rules, batch=(
                ("pod", "data") if "pod" in mesh.axis_names else ("data",)))
    # build the model AFTER all config adjustments (the step builder reads
    # model.config, e.g. train_microbatches)
    model = build_model(cfg)
    policy = ActivationPolicy(mesh=mesh,
                              batch_axes=rules.batch or None,
                              tensor_axis=rules.tensor,
                              seq_shard_hidden=seq_shard)

    t0 = time.time()
    abstract_params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pspecs = shd.param_specs(cfg, mesh, abstract_params, rules)
    p_shard = _spec_tree_to_shardings(mesh, pspecs)
    params_sds = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        abstract_params, p_shard)

    batch_abs = input_specs(cfg, shape)

    if shape.kind == "train":
        optimizer = adamw()
        abstract_opt = jax.eval_shape(optimizer.init, abstract_params)
        ospecs = {"mu": pspecs["mu"] if "mu" in pspecs else pspecs,
                  "nu": pspecs, "count": jax.sharding.PartitionSpec()}
        ospecs = shd.opt_specs(pspecs)
        o_shard = _spec_tree_to_shardings(mesh, ospecs)
        opt_sds = jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            abstract_opt, o_shard)
        bspecs = shd.batch_specs(cfg, mesh, batch_abs, rules)
        b_shard = _spec_tree_to_shardings(mesh, bspecs)
        batch_sds = jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            batch_abs, b_shard)
        fn = step_lib.make_train_step(model, optimizer, grad_accum=grad_accum)
        with mesh, activation_sharding(policy):
            lowered = jax.jit(fn, donate_argnums=(0, 1)).lower(
                params_sds, opt_sds, batch_sds)
    elif shape.kind == "prefill":
        bspecs = shd.batch_specs(cfg, mesh, batch_abs, rules)
        b_shard = _spec_tree_to_shardings(mesh, bspecs)
        batch_sds = jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            batch_abs, b_shard)
        fn = step_lib.make_prefill_step(model)
        with mesh, activation_sharding(policy):
            lowered = jax.jit(fn).lower(params_sds, batch_sds)
    else:  # decode
        abstract_cache = jax.eval_shape(
            lambda: model.init_cache(shape.global_batch, shape.seq_len))
        cspecs = shd.cache_specs(cfg, mesh, abstract_cache, shape.global_batch,
                                 cache_rules)
        c_shard = _spec_tree_to_shardings(mesh, cspecs)
        cache_sds = jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            abstract_cache, c_shard)
        tok_axes = rules.batch if rules.batch else None
        if tok_axes is not None and shape.global_batch % int(
                np.prod([mesh.shape[a] for a in tok_axes])) != 0:
            tok_axes = None
        tok_sds = jax.ShapeDtypeStruct(
            (shape.global_batch, 1), jnp.int32,
            sharding=jax.NamedSharding(
                mesh, jax.sharding.PartitionSpec(tok_axes)))
        pos_sds = jax.ShapeDtypeStruct((), jnp.int32)
        fn = step_lib.make_serve_step(model)
        with mesh, activation_sharding(policy):
            lowered = jax.jit(fn, donate_argnums=(1,)).lower(
                params_sds, cache_sds, tok_sds, pos_sds)

    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    from repro.launch.hlo_analysis import analyze_hlo
    hcost = analyze_hlo(hlo)

    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "num_devices": int(np.prod(list(mesh.shape.values()))),
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        # trip-count-aware per-device terms (see hlo_analysis.py)
        "flops": float(hcost.flops),
        "bytes_accessed": float(hcost.bytes),
        "collectives": {
            "bytes": {k: float(v) for k, v in hcost.collective_bytes.items()},
            "counts": {k: float(v) for k, v in hcost.collective_counts.items()},
            "total_bytes": float(hcost.total_collective_bytes),
        },
        # XLA's own numbers for reference (loop bodies counted once)
        "xla_flops": float(cost.get("flops", -1.0)) if cost else -1.0,
        "xla_bytes_accessed": float(cost.get("bytes accessed", -1.0)) if cost else -1.0,
        "memory": {
            k: int(getattr(mem, k))
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes")
            if mem is not None and hasattr(mem, k)
        },
        "params": get_config(arch).param_count(),
        "active_params": get_config(arch).active_param_count(),
    }
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=("single", "multi"), default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--use-flash", action="store_true",
                    help="lower the Pallas kernel path (TPU target only)")
    ap.add_argument("--out", default=None)
    ap.add_argument("--baseline", action="store_true",
                    help="paper-era baseline: flat MoE dispatch, "
                         "seq-sharded KV caches (for the §Perf A/B table)")
    args = ap.parse_args()

    mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))
    mesh_name = args.mesh

    cells = grid() if args.all else [(args.arch, args.shape)]
    out_path = pathlib.Path(
        args.out or f"benchmarks/artifacts/dryrun_{mesh_name}.json")
    out_path.parent.mkdir(parents=True, exist_ok=True)
    results = []
    if out_path.exists():
        results = json.loads(out_path.read_text())

    done = {(r["arch"], r["shape"], r["mesh"]) for r in results}
    for arch, shape_name in cells:
        skip = cell_is_skipped(arch, shape_name)
        if skip:
            print(f"SKIP {arch} x {shape_name}: {skip}")
            continue
        if (arch, shape_name, mesh_name) in done:
            print(f"CACHED {arch} x {shape_name} x {mesh_name}")
            continue
        print(f"=== {arch} x {shape_name} x {mesh_name} ===", flush=True)
        base_overrides = (
            {"_moe_flat": True, "_kv_seq": True,
             "decode_cache_in_carry": False} if args.baseline else {})
        try:
            rec = run_cell(arch, shape_name, mesh, mesh_name,
                           use_flash=args.use_flash,
                           extra_overrides=base_overrides)
        except Exception as e:  # noqa: BLE001 — report and continue the grid
            print(f"FAILED {arch} x {shape_name}: {type(e).__name__}: {e}",
                  flush=True)
            raise
        print(json.dumps({k: rec[k] for k in
                          ("flops", "bytes_accessed", "compile_s")},
                         indent=None), flush=True)
        print("  collectives:", rec["collectives"]["total_bytes"], "B", flush=True)
        if rec["memory"]:
            print("  memory:", rec["memory"], flush=True)
        results.append(rec)
        out_path.write_text(json.dumps(results, indent=1))
    print(f"wrote {out_path} ({len(results)} records)")


if __name__ == "__main__":
    main()
