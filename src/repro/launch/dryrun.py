import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# --------------------------------------------------------------------------
# Multi-pod dry run: lower + compile every (architecture × input shape) on
# the production meshes (16×16 single pod, 2×16×16 multi-pod), print
# memory/cost analysis, and extract roofline terms via unrolled shallow
# probes (see repro.roofline.analysis for the method).
#
# The XLA_FLAGS line above MUST run before any other import (jax locks the
# device count at first init); smoke tests and benches never import this
# module, so they see the single real CPU device.
# --------------------------------------------------------------------------

import argparse       # noqa: E402
import dataclasses    # noqa: E402
import json           # noqa: E402
import time           # noqa: E402
import traceback      # noqa: E402

import jax            # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.config import INPUT_SHAPES, ShapeSpec, TrainConfig, get_shape  # noqa: E402
from repro.configs import ASSIGNED, get_config  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models import get_model  # noqa: E402
from repro.roofline.analysis import (cost_from_compiled, probe_pair,  # noqa: E402
                                     roofline_from_cost, scan_corrections)
from repro.sharding import (cache_pspecs, input_pspecs, param_pspecs,  # noqa: E402
                            to_shardings)
from repro.sharding.hints import mesh_context  # noqa: E402
from repro.training import AdamW, jit_train_step  # noqa: E402


def _mem_dict(mem) -> dict:
    if mem is None:
        return {}
    fields = ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes")
    return {f: getattr(mem, f, None) for f in fields}


def lower_step(cfg, shape: ShapeSpec, mesh, dtype=jnp.bfloat16):
    """Build and lower the step for (cfg, shape) on mesh.  Returns lowered."""
    model = get_model(cfg)
    aparams = model.abstract_params(dtype)
    pshard = to_shardings(param_pspecs(cfg, aparams, mesh), mesh)

    if shape.kind == "train":
        batch = model.train_inputs(shape)
        opt = AdamW(TrainConfig())
        aopt = opt.abstract_init(aparams)
        fn, _ = jit_train_step(model, opt, mesh, aparams, batch, donate=False)
        return fn.lower(aparams, aopt, batch), model

    if shape.kind == "prefill":
        batch = model.train_inputs(shape)
        batch.pop("labels")
        cache = model.init_cache(shape.global_batch, shape.seq_len, dtype,
                                 abstract=True)
        bshard = to_shardings(input_pspecs(batch, mesh), mesh)
        cshard = to_shardings(cache_pspecs(cfg, cache, mesh), mesh)
        fn = jax.jit(lambda p, b, c: model.prefill(p, b, c),
                     in_shardings=(pshard, bshard, cshard))
        return fn.lower(aparams, batch, cache), model

    # decode
    tokens, cache = model.decode_inputs(shape, dtype)
    tshard = to_shardings(input_pspecs({"t": tokens}, mesh)["t"], mesh)
    cshard = to_shardings(cache_pspecs(cfg, cache, mesh), mesh)
    # production decode donates the cache: pass-through buffers alias the
    # outputs instead of being copied every step
    fn = jax.jit(lambda p, t, c: model.decode_step(p, t, c),
                 in_shardings=(pshard, tshard, cshard),
                 donate_argnums=(2,))
    return fn.lower(aparams, tokens, cache), model


def applicable(cfg, shape: ShapeSpec) -> bool:
    """long_500k needs sub-quadratic serving: SSM/hybrid run natively; dense/
    MoE/VLM/enc-dec run via their sliding-window serving variant (all
    configured); so every pair runs.  Kept as a hook for future skips."""
    return True


def run_pair(arch: str, shape_name: str, multi_pod: bool, probe: bool,
             outdir: str) -> dict:
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    mesh_name = "pod512" if multi_pod else "pod256"
    tag = f"{arch}_{shape_name}_{mesh_name}"
    path = os.path.join(outdir, tag + ".json")
    if os.path.exists(path):
        with open(path) as f:
            return json.load(f)

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "chips": chips, "ok": False}
    t0 = time.time()
    try:
        with mesh_context(mesh):
            lowered, model = lower_step(cfg, shape, mesh)
        rec["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)
        mem = compiled.memory_analysis()
        rec["memory"] = _mem_dict(mem)
        per_dev = sum(v for v in (mem.argument_size_in_bytes,
                                  mem.output_size_in_bytes,
                                  mem.temp_size_in_bytes) if v)
        rec["per_device_bytes"] = int(per_dev)
        rec["fits_16gb"] = bool(per_dev < 16e9)
        ca = compiled.cost_analysis()
        rec["raw_cost"] = {k: float(v) for k, v in ca.items()
                           if k in ("flops", "bytes accessed")}
        rec["ok"] = True

        if probe and not multi_pod:
            rec["roofline"] = run_probe(cfg, shape, mesh, chips)
    except Exception as e:  # noqa
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc(limit=8)
    rec["total_s"] = round(time.time() - t0, 2)

    os.makedirs(outdir, exist_ok=True)
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def run_probe(cfg, shape, mesh, chips) -> dict:
    """Unrolled shallow probes -> extrapolated per-device costs -> roofline."""
    cfg_a, cfg_b, K = probe_pair(cfg)
    costs = []
    for c in (cfg_a, cfg_b):
        with mesh_context(mesh):
            lowered, model = lower_step_probe(c, shape, mesh)
        costs.append(cost_from_compiled(lowered.compile()))
    full = costs[0].combine(costs[1], K)
    corr = scan_corrections(cfg, shape, chips)
    rl = roofline_from_cost(full, cfg, shape, chips, corr)
    return {
        "probe_K": K,
        "per_device_flops": full.flops + corr,
        "per_device_bytes": full.bytes_accessed,
        "collective_bytes": full.collective_bytes,
        "collective_counts": full.collective_counts,
        **rl.to_dict(),
    }


def lower_step_probe(cfg, shape, mesh, dtype=jnp.bfloat16):
    model = get_model(cfg)
    model.scan_unroll = True
    aparams = model.abstract_params(dtype)
    pshard = to_shardings(param_pspecs(cfg, aparams, mesh), mesh)
    if shape.kind == "train":
        batch = model.train_inputs(shape)
        opt = AdamW(TrainConfig())
        aopt = opt.abstract_init(aparams)
        fn, _ = jit_train_step(model, opt, mesh, aparams, batch, donate=False)
        return fn.lower(aparams, aopt, batch), model
    if shape.kind == "prefill":
        batch = model.train_inputs(shape)
        batch.pop("labels")
        cache = model.init_cache(shape.global_batch, shape.seq_len, dtype,
                                 abstract=True)
        bshard = to_shardings(input_pspecs(batch, mesh), mesh)
        cshard = to_shardings(cache_pspecs(cfg, cache, mesh), mesh)
        fn = jax.jit(lambda p, b, c: model.prefill(p, b, c),
                     in_shardings=(pshard, bshard, cshard))
        return fn.lower(aparams, batch, cache), model
    tokens, cache = model.decode_inputs(shape, dtype)
    tshard = to_shardings(input_pspecs({"t": tokens}, mesh)["t"], mesh)
    cshard = to_shardings(cache_pspecs(cfg, cache, mesh), mesh)
    # production decode donates the cache: pass-through buffers alias the
    # outputs instead of being copied every step
    fn = jax.jit(lambda p, t, c: model.decode_step(p, t, c),
                 in_shardings=(pshard, tshard, cshard),
                 donate_argnums=(2,))
    return fn.lower(aparams, tokens, cache), model


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="one arch id or 'all'")
    ap.add_argument("--shape", default=None,
                    help="one of train_4k/prefill_32k/decode_32k/long_500k")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--no-probe", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    archs = ASSIGNED if args.arch in (None, "all") else [args.arch]
    shapes = ([s.name for s in INPUT_SHAPES] if args.shape is None
              else [args.shape])
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    results = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                rec = run_pair(arch, shape, mp, probe=not args.no_probe,
                               outdir=args.out)
                status = "OK " if rec["ok"] else "FAIL"
                extra = ""
                if rec.get("roofline"):
                    rl = rec["roofline"]
                    extra = (f" bottleneck={rl['bottleneck']}"
                             f" c={rl['compute_s']*1e3:.2f}ms"
                             f" m={rl['memory_s']*1e3:.2f}ms"
                             f" x={rl['collective_s']*1e3:.2f}ms")
                if not rec["ok"]:
                    extra = " " + rec.get("error", "")[:120]
                print(f"{status} {arch:18s} {shape:12s} {rec['mesh']:7s} "
                      f"{rec.get('per_device_bytes', 0)/1e9:6.2f} GB/dev "
                      f"compile {rec.get('compile_s', 0):7.1f}s{extra}",
                      flush=True)
                results.append(rec)
    n_ok = sum(r["ok"] for r in results)
    print(f"\n{n_ok}/{len(results)} combinations lowered+compiled")
    return 0 if n_ok == len(results) else 1


if __name__ == "__main__":
    raise SystemExit(main())
