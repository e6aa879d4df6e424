import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count on first init). Everything below may import jax.

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
from typing import Any, Dict, Optional, Tuple  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import ARCH_IDS, SHAPES_BY_NAME, get_config  # noqa: E402
from repro.configs.base import ModelConfig, ShapeCell  # noqa: E402
from repro.distributed import sharding as shd  # noqa: E402
from repro.launch.mesh import make_production_mesh, make_mesh  # noqa: E402
from repro.models.model import Model, input_specs  # noqa: E402
from repro.optim import adamw  # noqa: E402

"""Multi-pod dry-run driver (deliverable e).

For every (architecture × input shape × mesh): build the step function
(train_step / prefill / decode), attach in/out shardings from
``repro.distributed.sharding``, ``.lower().compile()`` against
ShapeDtypeStruct inputs (no allocation), and record
``memory_analysis()`` + ``cost_analysis()`` + the collective-op byte
census parsed from the optimized HLO. Artifacts land in
``experiments/artifacts/dryrun/`` and feed §Roofline.
"""

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "experiments", "artifacts", "dryrun")

_COLLECTIVE_RE = re.compile(
    r"=\s*(\([^)]*\)|[a-z0-9]+\[[^\]]*\])\S*\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}


def collective_bytes(hlo_text: str) -> Dict[str, Any]:
    """Sum output bytes of every collective op in optimized HLO text."""
    per_op: Dict[str, float] = {}
    counts: Dict[str, int] = {}
    for m in _COLLECTIVE_RE.finditer(hlo_text):
        shape_part, op = m.group(1), m.group(2)
        if m.group(3) == "-start" and f"{op}-done" in hlo_text:
            pass  # count the -start (has the shape); -done lines don't match
        nbytes = 0
        for dm in _SHAPE_RE.finditer(shape_part):
            dt, dims = dm.group(1), dm.group(2)
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES[dt]
        per_op[op] = per_op.get(op, 0) + nbytes
        counts[op] = counts.get(op, 0) + 1
    return {"bytes_by_op": per_op, "counts": counts,
            "total_bytes": sum(per_op.values())}


def _cost_summary(cost: Dict[str, Any]) -> Dict[str, float]:
    out = {}
    for k in ("flops", "transcendentals", "bytes accessed"):
        if k in cost:
            out[k] = float(cost[k])
    return out


def calibration_depths(cfg: ModelConfig) -> Tuple[int, int]:
    """Two unrolled depths whose linear fit extrapolates to full depth.

    XLA cost analysis counts while-loop bodies once, so the scanned full
    compile under-reports FLOPs/bytes/collectives by ~the layer count. The
    dry-run therefore also compiles shallow *unrolled* variants at two
    depths; per-layer deltas are exact because layers are homogeneous
    within a family's repeat unit (super-block for xlstm, attn_every
    window for zamba2).
    """
    if cfg.family == "hybrid":
        u = cfg.attn_every
        return u, 2 * u
    if cfg.family == "ssm":
        u = cfg.mlstm_per_slstm + 1
        return u, 2 * u
    return 2, 4


def depth_variant(cfg: ModelConfig, depth: int) -> ModelConfig:
    kw = dict(num_layers=depth, scan_layers=False)
    if cfg.family == "encdec":
        kw["encoder_layers"] = depth
    return dataclasses.replace(cfg, **kw)


def build_step(cfg: ModelConfig, shape: ShapeCell, opt_state_dtype: str):
    """Returns (fn, abstract_args tuple, kind) for one cell."""
    model = Model(cfg)
    specs = input_specs(cfg, shape)
    abstract_params = model.init_abstract()
    if shape.kind == "train":
        opt_cfg = adamw.AdamWConfig(state_dtype=opt_state_dtype)
        abstract_opt = jax.eval_shape(
            lambda p: adamw.init_state(opt_cfg, p), abstract_params)

        def train_step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(model.loss)(params, batch)
            params, opt_state, metrics = adamw.apply_updates(
                opt_cfg, params, grads, opt_state)
            metrics["loss"] = loss
            return params, opt_state, metrics

        return train_step, (abstract_params, abstract_opt, specs["batch"]), "train"
    if shape.kind == "prefill":
        def prefill_step(params, inputs, cache):
            return model.prefill(params, inputs, cache)

        return prefill_step, (abstract_params, specs["inputs"], specs["cache"]), "prefill"
    if shape.kind == "decode":
        def decode_step(params, tokens, cache):
            return model.decode_step(params, tokens, cache)

        return decode_step, (abstract_params, specs["tokens"], specs["cache"]), "decode"
    raise ValueError(shape.kind)


def _compile_cell(cfg: ModelConfig, shape: ShapeCell, mesh,
                  opt_state_dtype: str):
    """Shard + lower + compile one (config, shape) on ``mesh``."""
    fn, abstract_args, kind = build_step(cfg, shape, opt_state_dtype)
    params_sh = shd.shard_params(abstract_args[0], mesh, cfg)
    if kind == "train":
        opt_sh = shd.shard_opt_state(abstract_args[1], params_sh, mesh)
        batch_sh = shd.shard_inputs(abstract_args[2], mesh, cfg, shape)
        in_sh = (params_sh, opt_sh, batch_sh)
        metrics_sh = {"grad_norm": NamedSharding(mesh, P()),
                      "loss": NamedSharding(mesh, P())}
        out_sh = (params_sh, opt_sh, metrics_sh)
    else:
        rest = abstract_args[1:]
        # last serve argument is always the cache tree
        rest_sh = tuple(
            shd.shard_inputs(a, mesh, cfg, shape, is_cache=(i == len(rest) - 1))
            for i, a in enumerate(rest))
        in_sh = (params_sh,) + rest_sh
        cache_sh = rest_sh[-1]
        logits_sh = NamedSharding(mesh, shd.data_spec(
            (shape.global_batch, 1, cfg.vocab_size), mesh, cfg,
            shape.global_batch))
        out_sh = (logits_sh, cache_sh)
    t0 = time.perf_counter()
    with jax.set_mesh(mesh):
        lowered = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh).lower(
            *abstract_args)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower
    return compiled, kind, t_lower, t_compile


def calibrate_cell(cfg: ModelConfig, shape: ShapeCell, mesh,
                   opt_state_dtype: str, verbose: bool = True) -> Dict[str, Any]:
    """Compile two shallow unrolled variants; record per-depth costs."""
    d1, d2 = calibration_depths(cfg)
    cal: Dict[str, Any] = {"depths": [d1, d2], "full_depth": cfg.num_layers,
                           "points": []}
    for d in (d1, d2):
        cfg_d = depth_variant(cfg, d)
        compiled, _, tl, tc = _compile_cell(cfg_d, shape, mesh, opt_state_dtype)
        cost = _cost_summary(compiled.cost_analysis())
        coll = collective_bytes(compiled.as_text())
        cal["points"].append({
            "depth": d, "cost": cost,
            "collective_total_bytes": coll["total_bytes"],
            "collective_bytes_by_op": coll["bytes_by_op"],
            "compile_s": round(tc, 2),
        })
        if verbose:
            print(f"  calib depth={d}: flops={cost.get('flops', 0):.3e} "
                  f"coll={coll['total_bytes']/1e9:.3f} GB ({tc:.1f}s)")
    return cal


def dryrun_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
                mesh=None, opt_state_dtype: Optional[str] = None,
                calibrate: bool = True, save: bool = True,
                verbose: bool = True) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = SHAPES_BY_NAME[shape_name]
    mesh_tag = "pod2" if multi_pod else "pod1"
    record: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_tag,
        "params": cfg.param_count(), "active_params": cfg.active_param_count(),
    }
    if not cfg.supports_shape(shape):
        record["status"] = "skipped"
        record["skip_reason"] = cfg.skip_reason(shape)
        if save:
            _save(record)
        return record

    if mesh is None:
        mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = int(mesh.devices.size)
    record["mesh_shape"] = {k: int(v) for k, v in mesh.shape.items()}
    if opt_state_dtype is None:
        # bf16 moments for ≥100B-param models (memory; DESIGN.md §4)
        opt_state_dtype = "bfloat16" if cfg.param_count() > 1e11 else "float32"
    record["opt_state_dtype"] = opt_state_dtype

    compiled, kind, t_lower, t_compile = _compile_cell(
        cfg, shape, mesh, opt_state_dtype)
    record.update(status="ok", kind=kind, devices=n_dev,
                  lower_s=round(t_lower, 2), compile_s=round(t_compile, 2))

    try:
        mem = compiled.memory_analysis()
        record["memory_analysis"] = {
            k: int(getattr(mem, k)) for k in (
                "argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "generated_code_size_in_bytes")
            if hasattr(mem, k)}
        print_mem = record["memory_analysis"]
    except Exception as e:  # CPU backend may not implement it
        record["memory_analysis"] = {"error": str(e)}
        print_mem = str(e)

    try:
        record["cost_analysis"] = _cost_summary(compiled.cost_analysis())
    except Exception as e:
        record["cost_analysis"] = {"error": str(e)}

    hlo = compiled.as_text()
    record["collectives"] = collective_bytes(hlo)
    record["hlo_bytes"] = len(hlo)
    del hlo, compiled

    if calibrate:
        try:
            record["calibration"] = calibrate_cell(
                cfg, shape, mesh, opt_state_dtype, verbose=verbose)
        except Exception as e:
            record["calibration"] = {"error": repr(e)}
            print(f"[dryrun] calibration failed for {arch}×{shape_name}: {e}",
                  file=sys.stderr)

    if verbose:
        print(f"[dryrun] {arch} × {shape_name} × {mesh_tag}: OK "
              f"(lower {t_lower:.1f}s, compile {t_compile:.1f}s)")
        print(f"  memory_analysis: {print_mem}")
        print(f"  cost_analysis: {record['cost_analysis']}")
        print(f"  collectives: {record['collectives']['counts']} "
              f"total {record['collectives']['total_bytes']/1e9:.3f} GB")
    if save:
        _save(record)
    return record


def _save(record: Dict[str, Any]) -> None:
    os.makedirs(ARTIFACT_DIR, exist_ok=True)
    name = f"{record['arch']}__{record['shape']}__{record['mesh']}.json"
    with open(os.path.join(ARTIFACT_DIR, name), "w") as f:
        json.dump(record, f, indent=1)


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", default="all", help="arch id or 'all'")
    p.add_argument("--shape", default="all", help="shape name or 'all'")
    p.add_argument("--multi-pod", action="store_true")
    p.add_argument("--both-meshes", action="store_true")
    p.add_argument("--mesh-shape", default=None,
                   help="debug override, e.g. '4,4' (axes data,model)")
    p.add_argument("--no-save", action="store_true")
    p.add_argument("--no-calibrate", action="store_true",
                   help="skip the unrolled two-depth cost calibration")
    args = p.parse_args()

    archs = list(ARCH_IDS) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES_BY_NAME) if args.shape == "all" else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    mesh = None
    if args.mesh_shape:
        dims = tuple(int(x) for x in args.mesh_shape.split(","))
        axes = ("data", "model") if len(dims) == 2 else ("pod", "data", "model")
        mesh = make_mesh(dims, axes)

    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                try:
                    # calibration (roofline terms) only on the single-pod
                    # mesh — the multi-pod pass proves the pod axis shards
                    dryrun_cell(arch, shape, multi_pod=mp, mesh=mesh,
                                save=not args.no_save,
                                calibrate=(not mp) and not args.no_calibrate)
                except Exception as e:
                    failures.append((arch, shape, mp, repr(e)))
                    print(f"[dryrun] {arch} × {shape} × "
                          f"{'pod2' if mp else 'pod1'}: FAILED — {e}",
                          file=sys.stderr)
    if failures:
        print(f"\n[dryrun] {len(failures)} FAILURES:", file=sys.stderr)
        for f in failures:
            print(f"  {f}", file=sys.stderr)
        sys.exit(1)
    print("\n[dryrun] all requested cells compiled.")


if __name__ == "__main__":
    main()
