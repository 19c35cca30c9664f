import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The three lines above MUST stay the first statements — jax locks the
platform and device count on first init, and the production meshes need
512 virtual host devices (on a machine with an accelerator too).

Per cell:
    with mesh:
        lowered  = jit(step, in_shardings=…, out_shardings=…).lower(*specs)
        compiled = lowered.compile()
        print(compiled.memory_analysis())   # proves it fits
        print(compiled.cost_analysis())     # FLOPs/bytes for §Roofline

and a JSON report (memory table + roofline terms + collective census) is
written under --out for EXPERIMENTS.md §Dry-run / §Roofline.

Usage:
  python -m repro.launch.dryrun --arch smollm-360m --shape train_4k
  python -m repro.launch.dryrun --all --multi-pod both --out results/dryrun
"""

import argparse
import dataclasses
import json
import time
import traceback
from typing import Optional

import jax

from repro.analysis.roofline import (
    TPU_V5E, model_flops_for, roofline_from_compiled)
from repro.configs import ARCH_NAMES, SHAPES, cell_applicable, get_config, shape_cell
from repro.dist.steps import (
    build_prefill_step, build_serve_step, build_train_step)
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import (
    decode_token_specs, effective_seq, prefill_input_specs, step_config,
    train_input_specs)


def _mesh_desc(mesh) -> str:
    return "x".join(f"{mesh.shape[a]}{a}" for a in mesh.axis_names)


def lower_cell(arch: str, shape: str, *, multi_pod: bool,
               variant_overrides: Optional[dict] = None,
               step_overrides: Optional[dict] = None):
    """Returns (lowered, compiled, context dict) for one cell."""
    cfg = get_config(arch)
    cell = shape_cell(shape)
    ok, reason = cell_applicable(cfg, cell)
    if not ok:
        return None, None, {"skip": reason}
    if variant_overrides:
        cfg = dataclasses.replace(cfg, **variant_overrides)

    mesh = make_production_mesh(multi_pod=multi_pod)
    scfg = step_config(cfg, cell)
    if step_overrides:
        scfg = dataclasses.replace(scfg, **step_overrides)
    seq = effective_seq(cfg, cell)

    with mesh:
        if cell.kind == "train":
            specs = train_input_specs(cfg, cell)
            bundle = build_train_step(cfg, mesh, scfg, specs)
            args = (bundle.aux["params_shape"], bundle.aux["opt_shape"],
                    specs, jax.ShapeDtypeStruct((), jax.numpy.int32.dtype))
            lowered = bundle.fn.lower(*args)
        elif cell.kind == "prefill":
            in_specs = prefill_input_specs(cfg, cell)
            fe = None
            if len(in_specs) == 2:
                fe = (cfg.frontend_tokens, cfg.frontend_dim)
            bundle = build_prefill_step(cfg, mesh, scfg, cell.global_batch,
                                        in_specs[0].shape[1],
                                        with_frontend=fe)
            lowered = bundle.fn.lower(bundle.aux["params_shape"], *in_specs)
        else:  # decode
            bundle = build_serve_step(cfg, mesh, scfg, cell.global_batch, seq)
            lowered = bundle.fn.lower(bundle.aux["params_shape"],
                                      bundle.aux["cache_shape"],
                                      decode_token_specs(cell))
        compiled = lowered.compile()
    return lowered, compiled, {
        "mesh": mesh, "cfg": cfg, "cell": cell, "scfg": scfg}


def run_cell(arch: str, shape: str, *, multi_pod: bool, out_dir: str,
             verbose: bool = True,
             variant: str = "baseline",
             variant_overrides: Optional[dict] = None,
             step_overrides: Optional[dict] = None) -> dict:
    tag = f"{arch}__{shape}__{'pod2' if multi_pod else 'pod1'}"
    if variant != "baseline":
        tag += f"__{variant}"
    t0 = time.time()
    try:
        lowered, compiled, ctx = lower_cell(
            arch, shape, multi_pod=multi_pod,
            variant_overrides=variant_overrides,
            step_overrides=step_overrides)
        if compiled is None:
            rec = {"arch": arch, "shape": shape,
                   "mesh": "pod2" if multi_pod else "pod1",
                   "variant": variant,
                   "status": "skip", "reason": ctx["skip"]}
        else:
            mesh, cfg, cell = ctx["mesh"], ctx["cfg"], ctx["cell"]
            chips = mesh.devices.size
            if verbose:
                print(compiled.memory_analysis())
                print(compiled.cost_analysis())
            seq_eff = effective_seq(cfg, cell)
            n_tok = (cell.global_batch if cell.kind == "decode"
                     else cell.global_batch * seq_eff)
            rep = roofline_from_compiled(
                compiled, arch=arch, shape=shape,
                mesh_desc=_mesh_desc(mesh), chips=chips,
                model_flops=model_flops_for(cfg, cell, n_tokens=n_tok))
            rec = rep.to_dict()
            rec.update(status="ok", variant=variant,
                       compile_s=round(time.time() - t0, 1),
                       hbm_limit=TPU_V5E.hbm_bytes)
    except Exception as e:
        rec = {"arch": arch, "shape": shape,
               "mesh": "pod2" if multi_pod else "pod1",
               "variant": variant, "status": "error",
               "error": f"{type(e).__name__}: {e}",
               "trace": traceback.format_exc()[-2000:]}
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, tag + ".json"), "w") as f:
            json.dump(rec, f, indent=1, default=str)
    status = rec["status"]
    extra = (f" dominant={rec.get('dominant')} compile={rec.get('compile_s')}s"
             if status == "ok" else
             f" {rec.get('reason', rec.get('error', ''))[:120]}")
    print(f"[dryrun] {tag}: {status}{extra}", flush=True)
    return rec


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--arch", choices=ARCH_NAMES)
    p.add_argument("--shape", choices=[s.name for s in SHAPES])
    p.add_argument("--all", action="store_true")
    p.add_argument("--multi-pod", choices=["off", "on", "both"], default="off")
    p.add_argument("--out", default="results/dryrun")
    p.add_argument("--quiet", action="store_true")
    args = p.parse_args()

    pods = {"off": [False], "on": [True], "both": [False, True]}[args.multi_pod]
    cells = []
    if args.all:
        for a in ARCH_NAMES:
            for s in SHAPES:
                cells.append((a, s.name))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    n_bad = 0
    for arch, shape in cells:
        for mp in pods:
            rec = run_cell(arch, shape, multi_pod=mp, out_dir=args.out,
                           verbose=not args.quiet)
            if rec["status"] == "error":
                n_bad += 1
    if n_bad:
        raise SystemExit(f"{n_bad} cells failed")


if __name__ == "__main__":
    main()
