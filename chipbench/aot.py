"""Compile a serving cell's programs for a described TPU v5e, without the
chip, and print what each needs of the device's memory.

    JAX_PLATFORMS=cpu python3 chipbench/aot.py --workload <cell> \
        [--max-batch N] [--max-seq S]

It compiles the decode step and the bulk prefill of the longest prompt,
at the cell's sizes or at the slots and ring length given (to size a
cell).  Nothing runs; a compile that passes is not a chip run.
"""

from __future__ import annotations

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
GIB = 1 << 30


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--topology", default="v5e:2x2")
    p.add_argument("--max-batch", type=int)
    p.add_argument("--max-seq", type=int)
    args = p.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # lower the Pallas kernels for the chip, not the CPU's interpreter
    os.environ.setdefault("REPRO_PALLAS_INTERPRET", "0")
    for path in (os.path.join(ROOT, "src"), ROOT):
        sys.path.insert(0, path)
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh

    from chipbench import program, run, traffic

    wl = run.load_json(HERE, "workloads", args.workload + ".json")
    hf = run.load_json(HERE, "configs", wl["config"] + ".json")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=args.topology)
    m = wl.get("mesh", {})
    d, t = int(m.get("data", 1)), int(m.get("model", 1))
    mesh = Mesh(np.array(topo.devices[:d * t]).reshape(d, t),
                ("data", "model"))
    # the kernel path the chip resolves to (the CPU would pick jnp)
    cfg = dataclasses.replace(program.model_config(hf), attn_impl="pallas")
    from repro.dist import steps
    from repro.dist.sharding import to_shardings

    def sds(shapes, specs):
        return jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sh), shapes, to_shardings(mesh, specs))

    def report(what, compiled):
        ma = compiled.memory_analysis()
        print(f"[aot] {args.workload} {what}: args "
              f"{ma.argument_size_in_bytes / GIB:.3f} GiB, temp "
              f"{ma.temp_size_in_bytes / GIB:.3f} GiB, out "
              f"{ma.output_size_in_bytes / GIB:.3f} GiB, aliased "
              f"{ma.alias_size_in_bytes / GIB:.3f} GiB; "
              f"{compiled.as_text().count('tpu_custom_call')} kernel call(s)",
              flush=True)

    scfg = steps.StepConfig()
    srv = wl["server"]
    b = args.max_batch or int(srv["max_batch"])
    ms = args.max_seq or int(srv["max_seq"])
    bundle = steps.build_serve_step(cfg, mesh, scfg, batch=b, max_seq=ms,
                                    sample=True)
    params = sds(bundle.aux["params_shape"], bundle.in_specs[0])
    cache = sds(bundle.aux["cache_shape"], bundle.in_specs[1])
    toks = jax.ShapeDtypeStruct((b,), jnp.int32)
    report(f"decode step {b} x {ms}",
           bundle.fn.lower(params, cache, toks).compile())
    s = max(traffic.load(wl["traffic"]).length_set(wl["traffic"]))
    pb = steps.build_prefill_step(cfg, mesh, scfg, batch=1, seq_len=s,
                                  cache_len=ms)
    report(f"bulk prefill 1 x {s}", pb.fn.lower(
        params, jax.ShapeDtypeStruct((1, s), jnp.int32)).compile())
    return 0


if __name__ == "__main__":
    sys.exit(main())
