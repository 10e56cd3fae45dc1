import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# Tests may shrink the placeholder device fleet (must happen pre-jax-init).
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count="
        + os.environ["REPRO_DRYRUN_DEVICES"]
    )

"""Multi-pod dry-run: lower + compile every (architecture x input shape)

cell on the production meshes, prove the memory fits, and extract the
roofline terms (FLOPs / bytes / collective bytes) from the compiled
artifact.  This is how the distribution config is proven coherent without
real hardware (no device allocation -- inputs are ShapeDtypeStructs).

Usage:
    python -m repro.launch.dryrun --arch olmo-1b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod] [--rules fsdp_tp]
Artifacts land in experiments/dryrun/<arch>__<shape>__<mesh>[__rules].json.
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax

from repro.analysis import roofline as rl
from repro.analysis.jaxpr_cost import step_flops
from repro.configs import (
    ARCH_IDS,
    SHAPES_BY_NAME,
    cell_applicable,
    get_config,
)
from repro.launch.mesh import make_production_mesh
from repro.parallel.sharding import NAMED_RULES
from repro.runtime.steps import make_step

ARTIFACT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def input_specs(arch: str, shape_name: str):
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    from repro.configs import get_config as _gc
    from repro.launch.mesh import make_production_mesh as _mesh
    from repro.parallel.sharding import RULES_FSDP_TP

    cfg = _gc(arch)
    shape = SHAPES_BY_NAME[shape_name]
    mesh = _mesh()
    _, specs, _, _ = make_step(cfg, shape, mesh, RULES_FSDP_TP)
    return specs


def run_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool = False,
    rules_name: str = "fsdp_tp",
    save: bool = True,
    master_weights: bool = False,
    kv_quant: bool = False,
    kv_ring: bool = False,
    mesh_override=None,          # (data, model) sizes; e.g. (8, 8) for a
                                 # 64-chip independent serving slice
    accum_steps: int = 1,        # gradient accumulation (train cells)
) -> dict:
    import dataclasses

    cfg = get_config(arch)
    if kv_quant:
        cfg = dataclasses.replace(cfg, kv_quant=True)
    if kv_ring:
        cfg = dataclasses.replace(cfg, kv_ring=True)
    shape = SHAPES_BY_NAME[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "rules": rules_name
        + ("+mw" if master_weights else "")
        + ("+kvq" if kv_quant else "")
        + ("+ring" if kv_ring else "")
        + (f"+acc{accum_steps}" if accum_steps > 1 else ""),
        "kind": shape.kind,
    }

    ok, why = cell_applicable(cfg, shape)
    if not ok:
        record.update(status="skipped", reason=why)
        return _finish(record, save)

    rules = NAMED_RULES[rules_name]
    if mesh_override:
        from repro.launch.mesh import make_mesh

        mesh = make_mesh(mesh_override, ("data", "model"))
        record["mesh"] = "pod" + "x".join(str(d) for d in mesh_override)
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.size

    opt_cfg = None
    if master_weights:
        from repro.optim import AdamWConfig

        opt_cfg = AdamWConfig(master_weights=True)

    t0 = time.time()
    try:
        step_fn, specs, in_sh, out_sh = make_step(
            cfg, shape, mesh, rules, opt_cfg=opt_cfg, accum_steps=accum_steps
        )
        # decode: donate the KV/SSM cache so the update aliases in place --
        # the output cache write then costs one token-slice, not the full
        # buffer (memory_analysis reports it as alias_bytes).
        donate = (1,) if shape.kind == "decode" else ()
        with mesh:
            lowered = jax.jit(
                step_fn, in_shardings=in_sh, out_shardings=out_sh,
                donate_argnums=donate,
            ).lower(*specs)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

            mem = compiled.memory_analysis()      # proves it fits
            cost = compiled.cost_analysis()       # raw XLA view (recorded)
            hlo = compiled.as_text()
            # Scan-aware global FLOPs from the jaxpr (see analysis docstring)
            flops_global = step_flops(step_fn, specs)
    except Exception as e:  # a failure here is a bug in our sharding
        record.update(
            status="failed",
            error=f"{type(e).__name__}: {e}",
            traceback=traceback.format_exc()[-4000:],
        )
        return _finish(record, save)

    colls = rl.parse_collectives(hlo)
    terms = rl.roofline(
        flops_global, mem, colls, rl.model_flops_global(cfg, shape), n_dev
    )

    record.update(
        status="ok",
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        devices=n_dev,
        memory={
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "total_per_device": (
                mem.argument_size_in_bytes
                + mem.output_size_in_bytes
                + mem.temp_size_in_bytes
                - mem.alias_size_in_bytes
            ),
        },
        cost_xla={k: v for k, v in cost.items() if "bytes" in k or "flops" in k},
        flops_global_jaxpr=flops_global,
        collectives={
            "bytes": colls.op_bytes,
            "counts": colls.op_counts,
            "total": colls.total_bytes,
            "wire": colls.wire_bytes,
        },
        roofline=terms.as_dict(),
        params_global=cfg.param_count(),
        params_active=cfg.active_param_count(),
    )
    return _finish(record, save)


def _finish(record: dict, save: bool) -> dict:
    if save:
        ARTIFACT_DIR.mkdir(parents=True, exist_ok=True)
        suffix = "" if record["rules"] == "fsdp_tp" else f"__{record['rules']}"
        path = ARTIFACT_DIR / (
            f"{record['arch']}__{record['shape']}__{record['mesh']}{suffix}.json"
        )
        path.write_text(json.dumps(record, indent=1))
        record["artifact"] = str(path)
    return record


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=sorted(SHAPES_BY_NAME))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--rules", default="fsdp_tp", choices=sorted(NAMED_RULES))
    ap.add_argument("--master-weights", action="store_true",
                    help="bf16 params + f32 master in opt state (train)")
    ap.add_argument("--kv-quant", action="store_true",
                    help="int8 KV cache with power-of-two scales (decode)")
    ap.add_argument("--kv-ring", action="store_true",
                    help="window-sized ring-buffer KV cache (pure-SWA archs)")
    ap.add_argument("--mesh", default=None,
                    help="override mesh as 'data,model' (e.g. '8,8' = one "
                         "64-chip serving slice of the pod)")
    ap.add_argument("--accum", type=int, default=1,
                    help="gradient-accumulation microbatches (train cells)")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    cells = []
    if args.all:
        for a in ARCH_IDS:
            for s in SHAPES_BY_NAME:
                cells.append((a, s))
    else:
        if not (args.arch and args.shape):
            ap.error("--arch/--shape or --all required")
        cells = [(args.arch, args.shape)]

    failures = 0
    for arch, shape in cells:
        mesh_name = "pod2x16x16" if args.multi_pod else "pod16x16"
        suffix = "" if args.rules == "fsdp_tp" else f"__{args.rules}"
        path = ARTIFACT_DIR / f"{arch}__{shape}__{mesh_name}{suffix}.json"
        if args.skip_existing and path.exists():
            prev = json.loads(path.read_text())
            if prev.get("status") in ("ok", "skipped"):
                print(f"[skip-existing] {arch} {shape} {prev['status']}")
                continue
        rec = run_cell(
            arch, shape, args.multi_pod, args.rules,
            master_weights=args.master_weights,
            kv_quant=args.kv_quant,
            kv_ring=args.kv_ring,
            mesh_override=(
                tuple(int(x) for x in args.mesh.split(","))
                if args.mesh else None
            ),
            accum_steps=args.accum,
        )
        if rec["status"] == "ok":
            r = rec["roofline"]
            print(
                f"[ok] {arch:22s} {shape:12s} {mesh_name}  "
                f"compile={rec['compile_s']:.0f}s  "
                f"mem/dev={rec['memory']['total_per_device']/2**30:.2f}GiB  "
                f"terms(ms): c={r['compute_s']*1e3:.2f} "
                f"m={r['memory_s']*1e3:.2f} n={r['collective_s']*1e3:.2f} "
                f"dom={r['dominant']}"
            )
        elif rec["status"] == "skipped":
            print(f"[skipped] {arch} {shape}: {rec['reason']}")
        else:
            failures += 1
            print(f"[FAILED] {arch} {shape}: {rec['error']}")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
