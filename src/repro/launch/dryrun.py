# Multi-pod dry-run: AOT lower + compile of every (arch × shape × mesh) cell.
#
# Proves — without hardware — that the distribution config is coherent:
# sharding propagates, the collectives are supported, and the per-device
# memory fits.  The compiled artifact also feeds the roofline analysis
# (benchmarks/roofline.py) via ``cost_analysis`` + the collective-bytes parse.
#
# ``main`` asks for 512 placeholder CPU devices before jax initialises its
# backend (jax locks the device count then); importing this module sets
# nothing, so tests that import its helpers see the real devices.
#
# Usage::
#
#   PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b --shape train_4k
#   PYTHONPATH=src python -m repro.launch.dryrun --all            # 40 cells x 2 meshes
#   PYTHONPATH=src python -m repro.launch.dryrun --all --mesh single

import argparse
import json
import re
import time
import traceback
from pathlib import Path
from typing import Any, Dict, Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import ARCH_NAMES, SHAPES, cell_is_runnable, get_config
from ..configs.base import ArchConfig, ShapeConfig
from ..distributed.sharding import force_host_device_count, param_pspecs
from ..models.counting import model_flops_per_token, param_count
from ..optim.optimizers import OptState
from .mesh import make_production_mesh
from .specs import abstract_params, batch_pspecs, input_specs
from .steps import make_optimizer, make_prefill_step, make_serve_step, make_train_step

RESULTS_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"=\s*(?:\([^)]*\)|(\w+)\[([0-9,]*)\])\s*(\S+)\(")
_TUPLE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")


def _bytes_of(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def macro_bytes(hlo_text: str) -> int:
    """TPU-fusion-adjusted HBM-traffic estimate from post-SPMD HLO.

    XLA-CPU's ``bytes accessed`` counts every elementwise/copy/reshape op at
    full size; on TPU those fuse into neighbouring matmuls and never touch
    HBM.  This proxy counts only the ops whose traffic survives fusion:

      * dot / convolution (and oneDNN matmul custom-calls): A + B + C bytes
      * gather / dynamic-slice: 2 x result (read the slice, write it)
      * scatter / dynamic-update-slice: 2 x update (in-place on TPU)

    It remains an upper bound for attention (the shipped Pallas flash kernel
    keeps the score matrix in VMEM; this counts it) — noted in EXPERIMENTS.md.
    """
    total = 0
    for raw in hlo_text.splitlines():
        line = raw.strip()
        if "=" not in line or "(" not in line:
            continue
        shapes = _TUPLE_RE.findall(line.split("metadata=")[0])
        if not shapes:
            continue
        if (" dot(" in line or " convolution(" in line
                or ("custom-call" in line and "matmul" in line)):
            total += sum(_bytes_of(dt, dims) for dt, dims in shapes)
        elif " gather(" in line or " dynamic-slice(" in line:
            total += 2 * _bytes_of(*shapes[0])
        elif " scatter(" in line or " dynamic-update-slice(" in line:
            upd = shapes[2] if len(shapes) > 2 else shapes[0]
            total += 2 * _bytes_of(*upd)
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum result-shape bytes of every collective op in post-SPMD HLO.

    Per-op result shapes are a proxy for link traffic (exact up to the
    ring-algorithm factor 2(n-1)/n, noted in EXPERIMENTS.md §Roofline).
    Collectives inside while-loop bodies appear once — the roofline harness
    extrapolates per-layer costs from unrolled lowers (see
    benchmarks/roofline.py) so scan bodies never hide traffic.
    """
    out = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip().lstrip("%")
        for coll in _COLLECTIVES:
            # match ops like: %ar = f32[128]{0} all-reduce(...), or tuple-shaped
            if f" {coll}(" in stripped or f"= {coll}(" in stripped.replace("  ", " "):
                head = stripped.split(f" {coll}(")[0]
                total = sum(_bytes_of(dt, dims) for dt, dims in _TUPLE_RE.findall(head))
                out[coll] += total
                break
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


def _named(mesh, pspec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), pspec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def opt_pspecs(params_pspecs):
    return OptState(P(), params_pspecs, params_pspecs)


def lower_cell(cfg: ArchConfig, shape: ShapeConfig, mesh,
               include_optimizer: bool = True):
    """Lower the step for one cell under ``mesh``.  Returns (lowered, kind)."""
    with jax.set_mesh(mesh):
        specs = input_specs(cfg, shape)
        bspecs = batch_pspecs(specs, mesh)
        params_sds = abstract_params(cfg)
        ppspecs = param_pspecs(params_sds, cfg.num_experts)
        pns = _named(mesh, ppspecs)
        bns = _named(mesh, bspecs)

        if shape.kind == "train":
            opt_init, opt_update = make_optimizer(cfg)
            opt_sds = jax.eval_shape(opt_init, params_sds)
            ons = _named(mesh, opt_pspecs(ppspecs))
            step = make_train_step(cfg, opt_update)
            jitted = jax.jit(step, in_shardings=(pns, ons, bns),
                             out_shardings=(pns, ons, None),
                             donate_argnums=(0, 1))
            return jitted.lower(params_sds, opt_sds, specs), "train_step"

        if shape.kind == "prefill":
            step = make_prefill_step(cfg)
            jitted = jax.jit(step, in_shardings=(pns, bns))
            return jitted.lower(params_sds, specs), "prefill_step"

        # decode — pure-TP weights when params/TP fit a ~8 GiB HBM budget
        # (otherwise keep the FSDP factor; §Perf iteration D1)
        from ..models.counting import param_count

        tp_n = dict(mesh.shape).get("model", 1)
        pure_tp = (param_count(cfg) * 2 / tp_n) <= 8 * 2**30
        if pure_tp:
            pns = _named(mesh, param_pspecs(params_sds, cfg.num_experts,
                                            serve_pure_tp=True))
        step = make_serve_step(cfg)
        cns = bns.pop("caches")
        token_ns, pos_ns = bns["token"], bns["pos"]
        jitted = jax.jit(step, in_shardings=(pns, cns, token_ns, pos_ns),
                         donate_argnums=(1,))
        return jitted.lower(params_sds, specs["caches"], specs["token"],
                            specs["pos"]), "serve_step"


def analyze(lowered) -> Dict[str, Any]:
    t0 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t0
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    text = compiled.as_text()
    coll = collective_bytes(text)
    return {
        "compile_seconds": round(compile_s, 1),
        "flops": cost.get("flops", 0.0),
        "bytes_accessed": cost.get("bytes accessed", 0.0),
        "macro_bytes": macro_bytes(text),
        "collective_bytes": coll,
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_bytes": mem.peak_memory_in_bytes,
            "code_bytes": mem.generated_code_size_in_bytes,
        },
    }


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             force: bool = False, overrides: Optional[Dict[str, Any]] = None,
             variant: str = "") -> Dict[str, Any]:
    import dataclasses

    cfg = get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    if variant:
        mesh_name = f"{mesh_name}__{variant}"
    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    out_path = RESULTS_DIR / f"{arch}__{shape_name}__{mesh_name}.json"
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())

    record: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "params": param_count(cfg), "active_params": param_count(cfg, True),
        "model_flops_per_token": model_flops_per_token(cfg),
    }
    runnable, why = cell_is_runnable(cfg, shape_name)
    if not runnable:
        record["status"] = "skipped"
        record["reason"] = why
        out_path.write_text(json.dumps(record, indent=2))
        return record

    mesh = make_production_mesh(multi_pod=multi_pod)
    record["devices"] = mesh.size
    try:
        lowered, kind = lower_cell(cfg, shape, mesh)
        record["step_kind"] = kind
        record.update(analyze(lowered))
        record["status"] = "ok"
    except Exception as e:  # noqa: BLE001 — a failed cell is a bug report
        record["status"] = "failed"
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-4000:]
    out_path.write_text(json.dumps(record, indent=2))
    return record


def main(argv: Optional[list] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCH_NAMES)
    ap.add_argument("--shape", choices=sorted(SHAPES))
    ap.add_argument("--mesh", choices=("single", "multi", "both"), default="both")
    ap.add_argument("--all", action="store_true", help="run every cell")
    ap.add_argument("--force", action="store_true", help="recompute cached cells")
    ap.add_argument("--set", action="append", default=[],
                    help="ArchConfig override key=value (hillclimb lever)")
    ap.add_argument("--variant", default="", help="label for override runs")
    args = ap.parse_args(argv)
    force_host_device_count(512)

    overrides: Dict[str, Any] = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        overrides[k] = {"true": True, "false": False}.get(v.lower(), v)
        if isinstance(overrides[k], str):
            try:
                overrides[k] = int(v)
            except ValueError:
                pass

    archs = ARCH_NAMES if (args.all or not args.arch) else [args.arch]
    shapes = sorted(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    failures = 0
    for arch in archs:
        for shape_name in shapes:
            for multi in meshes:
                r = run_cell(arch, shape_name, multi, force=args.force,
                             overrides=overrides or None, variant=args.variant)
                tag = f"{arch} × {shape_name} × {r['mesh']}"
                if r["status"] == "ok":
                    gb = r["memory"]["peak_bytes"] / 2**30
                    print(f"[ok]      {tag}: peak {gb:.2f} GiB/dev, "
                          f"flops {r['flops']:.3e}, "
                          f"coll {r['collective_bytes']['total']:.3e} B, "
                          f"compile {r['compile_seconds']}s", flush=True)
                elif r["status"] == "skipped":
                    print(f"[skip]    {tag}: {r['reason']}", flush=True)
                else:
                    failures += 1
                    print(f"[FAILED]  {tag}: {r['error']}", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
