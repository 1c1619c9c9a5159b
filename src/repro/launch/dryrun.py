import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes and extract memory/cost/collective analysis.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-0.6b \
        --shape train_4k [--multi-pod] [--all] [--out experiments/dryrun]

The XLA_FLAGS line above MUST precede every other import (jax locks the
device count at first init); smoke tests and benches never import this
module, so they see the real single CPU device.
"""

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ALL_ARCHS, get_config
from repro.configs.base import ModelConfig
from repro.core.memory.static_estimator import (active_param_count,
                                                param_count)
from repro.launch.analysis import (ROOFLINE_HEADER, Roofline,
                                   analytic_hbm_bytes)
from repro.launch.hlo_parse import analyze as analyze_hlo
from repro.launch.mesh import make_production_mesh
from repro.launch.shapes import SHAPES, ShapePreset, applicable, input_specs
from repro.models import registry
from repro.sharding.partitioning import (LONG_CONTEXT_OVERRIDES,
                                         active_act_rules, apply_policy,
                                         spec_for)
from repro.training.optimizer import AdamWConfig
from repro.training.train_step import make_train_step

BIG_PARAM_THRESHOLD = 50e9  # bf16 optimizer moments above this (DESIGN.md)

#: gradient-accumulation depth overrides: the >=300B MoE models need
#: microbatch=16 (activation carries halve) to fit a single v5e pod
MICRO_OVERRIDES = {"grok-1-314b": 16, "llama4-maverick-400b-a17b": 16,
                   "gemma3-27b": 16}


# -- sharding builders -----------------------------------------------------------


def _shard_tree(shapes_tree, specs_tree, mesh, rules, long_context):
    ov = LONG_CONTEXT_OVERRIDES if long_context else None

    def one(shape_struct, axes):
        pspec = spec_for(tuple(axes), mesh, tuple(shape_struct.shape),
                         rules, ov)
        return NamedSharding(mesh, pspec)

    return jax.tree_util.tree_map(
        one, shapes_tree, specs_tree,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def _replicated(mesh):
    return NamedSharding(mesh, P())


# -- per-kind lowering ------------------------------------------------------------


def lower_train(cfg: ModelConfig, preset: ShapePreset, mesh,
                policy: str = "baseline"):
    prules, arules = apply_policy(policy)
    param_shapes, param_specs = registry.abstract_params(cfg)
    big = param_count(cfg) > BIG_PARAM_THRESHOLD / 2
    mdtype = jnp.bfloat16 if big else jnp.float32
    mzeros = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, mdtype), param_shapes)
    state_shapes = {"params": param_shapes,
                    "opt": {"m": mzeros, "v": mzeros,
                            "step": jax.ShapeDtypeStruct((), jnp.int32)}}
    p_sh = _shard_tree(param_shapes, param_specs, mesh, prules, False)
    state_sh = {"params": p_sh,
                "opt": {"m": p_sh, "v": p_sh, "step": _replicated(mesh)}}

    batch_shapes = input_specs(cfg, preset)
    b_specs = registry.batch_specs(cfg, with_labels=True)
    b_sh = _shard_tree(batch_shapes, b_specs, mesh, arules, False)

    step = make_train_step(cfg, AdamWConfig(),
                           n_microbatches=preset.microbatches)
    jitted = jax.jit(step, in_shardings=(state_sh, b_sh),
                     out_shardings=(state_sh, None), donate_argnums=(0,))
    with active_act_rules(arules):
        return jitted.lower(state_shapes, batch_shapes)


def lower_prefill(cfg: ModelConfig, preset: ShapePreset, mesh,
                  policy: str = "baseline"):
    prules, arules = apply_policy(policy)
    param_shapes, param_specs = registry.abstract_params(cfg)
    p_sh = _shard_tree(param_shapes, param_specs, mesh, prules, False)
    batch_shapes = input_specs(cfg, preset)
    b_specs = registry.batch_specs(cfg, with_labels=False)
    b_sh = _shard_tree(batch_shapes, b_specs, mesh, arules,
                       preset.long_context)
    def fn(p, b):
        return registry.prefill(p, cfg, b)
    jitted = jax.jit(fn, in_shardings=(p_sh, b_sh))
    with active_act_rules(arules):
        return jitted.lower(param_shapes, batch_shapes)


def lower_decode(cfg: ModelConfig, preset: ShapePreset, mesh,
                 policy: str = "baseline"):
    prules, arules = apply_policy(policy)
    param_shapes, param_specs = registry.abstract_params(cfg)
    p_sh = _shard_tree(param_shapes, param_specs, mesh, prules, False)
    cache_shapes = jax.eval_shape(
        lambda: registry.init_caches(cfg, preset.batch, preset.seq))
    c_sh = _shard_tree(cache_shapes, registry.cache_specs(cfg), mesh,
                       arules, preset.long_context)
    tok = jax.ShapeDtypeStruct((preset.batch, 1), jnp.int32)
    tok_sh = NamedSharding(mesh, spec_for(
        ("batch", None), mesh, tok.shape, arules,
        LONG_CONTEXT_OVERRIDES if preset.long_context else None))
    idx = jax.ShapeDtypeStruct((), jnp.int32)

    def fn(p, t, i, c):
        return registry.decode_step(p, cfg, t, i, c)
    jitted = jax.jit(fn,
                     in_shardings=(p_sh, tok_sh, _replicated(mesh), c_sh),
                     out_shardings=(None, c_sh), donate_argnums=(3,))
    with active_act_rules(arules):
        return jitted.lower(param_shapes, tok, idx, cache_shapes)


LOWER = {"train": lower_train, "prefill": lower_prefill,
         "decode": lower_decode}


# -- the dry-run driver ---------------------------------------------------------------


@dataclasses.dataclass
class DryRunResult:
    arch: str
    shape: str
    mesh: str
    ok: bool
    policy: str = "baseline"
    skipped: str = ""
    error: str = ""
    compile_s: float = 0.0
    per_device_bytes: int = 0
    argument_bytes: int = 0
    temp_bytes: int = 0
    output_bytes: int = 0
    flops: float = 0.0            # HLO-parsed, trip-count-corrected, per dev
    raw_cost_flops: float = 0.0   # cost_analysis() figure (scan bodies x1)
    hbm_bytes: float = 0.0        # analytic per-device traffic (memory term)
    parsed_out_bytes: float = 0.0 # HLO byte proxy (diagnostic)
    collectives: dict | None = None
    model_flops: float = 0.0


def run_combo(arch: str, shape_name: str, multi_pod: bool,
              keep_hlo: str | None = None,
              policy: str = "baseline",
              microbatches: int | None = None,
              config_overrides: dict | None = None) -> DryRunResult:
    cfg = get_config(arch)
    if config_overrides:
        cfg = dataclasses.replace(cfg, **config_overrides)
    preset = SHAPES[shape_name]
    mesh_name = "2x16x16" if multi_pod else "16x16"
    res = DryRunResult(arch=arch, shape=shape_name, mesh=mesh_name, ok=False,
                       policy=policy)

    runs, why = applicable(cfg, preset)
    if not runs:
        res.skipped = why
        return res
    if preset.kind == "train" and arch in MICRO_OVERRIDES:
        preset = dataclasses.replace(preset,
                                     microbatches=MICRO_OVERRIDES[arch])
    if microbatches is not None and preset.kind == "train":
        preset = dataclasses.replace(preset, microbatches=microbatches)

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    t0 = time.time()
    try:
        with mesh:
            lowered = LOWER[preset.kind](cfg, preset, mesh, policy=policy)
            compiled = lowered.compile()
        res.compile_s = time.time() - t0
        try:
            ma = compiled.memory_analysis()
            res.argument_bytes = int(getattr(ma, "argument_size_in_bytes", 0))
            res.temp_bytes = int(getattr(ma, "temp_size_in_bytes", 0))
            res.output_bytes = int(getattr(ma, "output_size_in_bytes", 0))
            alias = int(getattr(ma, "alias_size_in_bytes", 0))
            res.per_device_bytes = (res.argument_bytes + res.temp_bytes
                                    + res.output_bytes - alias)
        except Exception:
            pass
        try:
            ca = compiled.cost_analysis()
            ca = ca[0] if isinstance(ca, (list, tuple)) else ca
            res.raw_cost_flops = float(ca.get("flops", 0.0))
        except Exception:
            pass
        try:
            hlo = compiled.as_text()
            parsed = analyze_hlo(hlo)
            res.flops = parsed["flops"]
            res.parsed_out_bytes = parsed["out_bytes"]
            res.collectives = parsed["collectives"]
            if keep_hlo:
                with open(keep_hlo, "w") as f:
                    f.write(hlo)
        except Exception as e:
            res.collectives = {"total": 0, "error": str(e)[:200]}
        # analytic useful FLOPs (per device): 6*N*D for train (fwd+bwd),
        # 2*N*D for prefill, 2*N per token for decode
        from repro.core.memory.static_estimator import (
            activation_bytes_train, kv_cache_bytes)
        n_active = active_param_count(cfg)
        n_total = param_count(cfg)
        tokens = preset.batch * (preset.seq if preset.kind != "decode" else 1)
        mult = 6 if preset.kind == "train" else 2
        res.model_flops = mult * n_active * tokens / n_dev
        opt_b = n_total * (2 * 2 if n_total > BIG_PARAM_THRESHOLD / 2
                           else 2 * 4)
        act_b = activation_bytes_train(
            cfg, preset.batch // (preset.microbatches
                                  if preset.kind == "train" else 1),
            preset.seq)
        cache_b = kv_cache_bytes(cfg, preset.batch, preset.seq)
        res.hbm_bytes = analytic_hbm_bytes(
            cfg, preset, n_dev, params_bytes=n_total * 2,
            opt_bytes=opt_b, cache_bytes=cache_b, act_bytes=act_b)
        res.ok = True
    except Exception as e:
        res.error = f"{type(e).__name__}: {e}"[:2000]
        res.compile_s = time.time() - t0
    return res


def roofline_of(res) -> Roofline:
    get = (lambda k, d=0.0: res.get(k, d)) if isinstance(res, dict) \
        else (lambda k, d=0.0: getattr(res, k, d))
    colls = get("collectives") or {}
    return Roofline(arch=get("arch"), shape=get("shape"), mesh=get("mesh"),
                    hlo_flops=get("flops"), hlo_bytes=get("hbm_bytes"),
                    coll_bytes=colls.get("total", 0),
                    model_flops=get("model_flops"))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=ALL_ARCHS + [None])
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    archs = ALL_ARCHS if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) \
        else [args.shape]
    meshes = [False, True] if (args.both_meshes or args.all) \
        else [args.multi_pod]

    os.makedirs(args.out, exist_ok=True)
    results = []
    print(ROOFLINE_HEADER)
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                res = run_combo(arch, shape, mp)
                results.append(dataclasses.asdict(res))
                tag = f"{arch} x {shape} x {res.mesh}"
                if res.skipped:
                    print(f"SKIP  {tag}: {res.skipped}")
                elif not res.ok:
                    print(f"FAIL  {tag}: {res.error[:300]}")
                else:
                    print(roofline_of(res).row()
                          + f"  [{res.compile_s:.0f}s compile, "
                          f"{res.per_device_bytes / 2**30:.2f} GiB/dev]")
                with open(os.path.join(args.out, "dryrun.json"), "w") as f:
                    json.dump(results, f, indent=1)
    n_ok = sum(1 for r in results if r["ok"])
    n_skip = sum(1 for r in results if r["skipped"])
    n_fail = len(results) - n_ok - n_skip
    print(f"\n{n_ok} ok / {n_skip} skipped / {n_fail} FAILED "
          f"(results -> {args.out}/dryrun.json)")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
