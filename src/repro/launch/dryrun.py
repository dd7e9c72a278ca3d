"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell on
the production meshes and record memory/cost/collective evidence.

Run as a script, it sets ``XLA_FLAGS`` for 512 placeholder host
devices before JAX first initializes its backend; importing the module
changes nothing.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                 # full matrix
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-0.5b \
      --shape train_4k --multi-pod --save-hlo
Artifacts land in experiments/dryrun/*.json (+ .hlo.gz with --save-hlo).
"""

import argparse
import dataclasses
import gzip
import json
import os
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import get_config, list_archs
from repro.launch.mesh import make_production_mesh
from repro.launch import shardings as sh
from repro.launch import specs as sp
from repro.distributed.sharding import make_rules, use_rules
from repro.models import build
from repro.optim import adamw
from repro.roofline import analysis as ra

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def _mesh_tag(multi_pod: bool) -> str:
    return "pod2x16x16" if multi_pod else "pod16x16"


def build_cell(arch: str, shape: str, multi_pod: bool, **overrides):
    """Returns (lowered, compiled, meta) for one cell."""
    cfg = get_config(arch, **overrides)
    spec = sp.cell_spec(cfg, shape)
    if not spec.runnable:
        return None, None, {"skip_reason": spec.skip_reason}

    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = make_rules(mesh, n_heads=cfg.n_heads,
                       n_kv_heads=cfg.n_kv_heads)
    model = build(cfg)
    meta = {"arch": arch, "shape": shape, "mesh": _mesh_tag(multi_pod),
            "kind": spec.kind, "batch": spec.batch,
            "seq_len": spec.seq_len}

    with use_rules(rules):
        if spec.kind == "train":
            opt = adamw(3e-4)
            p_shape = jax.eval_shape(
                lambda: model.init(jax.random.PRNGKey(0)))
            o_shape = jax.eval_shape(opt.init, p_shape)
            state_shape = {"params": p_shape, "opt": o_shape}
            batch = sp.batch_specs(cfg, spec)

            p_sh = sh.param_shardings(rules, p_shape)

            def train_step(state, batch):
                def lfn(p):
                    return model.loss(p, batch)
                (loss, met), grads = jax.value_and_grad(
                    lfn, has_aux=True)(state["params"])
                # pin grads to the parameter storage layout BEFORE the
                # optimizer: otherwise a replicated grad (e.g. the embed
                # scatter) drags the whole Adam update replicated
                # (qwen1.5-110b: 6 x 4.6GB f32 embed buffers)
                grads = jax.tree.map(
                    jax.lax.with_sharding_constraint, grads, p_sh)
                new_p, new_o = opt.update(grads, state["opt"],
                                          state["params"])
                return ({"params": new_p, "opt": new_o},
                        {"loss": loss, **met})

            state_sh = {"params": sh.param_shardings(rules, p_shape),
                        "opt": sh.opt_shardings(rules, o_shape)}
            in_sh = (state_sh, sh.batch_shardings(rules, batch))
            # out_shardings pin the updated state back to storage layout —
            # otherwise grads/updates inherit compute-view shardings
            # (e.g. expert grads replicated over the data axis: +26GB/dev)
            metric_sh = rules.sharding()
            out_sh = (state_sh, {"loss": metric_sh, "ce": metric_sh,
                                 "aux": metric_sh})
            fn = jax.jit(train_step, in_shardings=in_sh,
                         out_shardings=out_sh, donate_argnums=(0,))
            lowered = fn.lower(state_shape, batch)

        elif spec.kind == "prefill":
            p_shape = jax.eval_shape(
                lambda: model.init(jax.random.PRNGKey(0)))
            batch = sp.batch_specs(cfg, spec)

            def prefill_step(params, batch):
                return model.prefill(params, batch)

            in_sh = (sh.param_shardings(rules, p_shape),
                     sh.batch_shardings(rules, batch))
            lowered = jax.jit(prefill_step,
                              in_shardings=in_sh).lower(p_shape, batch)

        else:  # decode / serve_step
            p_shape = jax.eval_shape(
                lambda: model.init(jax.random.PRNGKey(0)))
            cache, token, pos = sp.decode_specs(cfg, spec, model)

            def serve_step(params, cache, token, pos):
                return model.decode_step(params, cache, token, pos)

            in_sh = (sh.param_shardings(rules, p_shape),
                     sh.cache_shardings(rules, cache),
                     sh.batch_shardings(rules, {"t": token})["t"],
                     rules.sharding())
            lowered = jax.jit(serve_step, in_shardings=in_sh,
                              donate_argnums=(1,)).lower(
                                  p_shape, cache, token, pos)

        compiled = lowered.compile()
    return lowered, compiled, meta


def run_cell(arch: str, shape: str, multi_pod: bool,
             save_hlo: bool = False, **overrides) -> dict:
    t0 = time.time()
    try:
        lowered, compiled, meta = build_cell(arch, shape, multi_pod,
                                             **overrides)
    except Exception as e:  # a failing cell is a bug — surface it loudly
        return {"arch": arch, "shape": shape,
                "mesh": _mesh_tag(multi_pod), "status": "ERROR",
                "error": f"{type(e).__name__}: {e}",
                "trace": traceback.format_exc()[-2000:]}
    if lowered is None:
        return {"arch": arch, "shape": shape,
                "mesh": _mesh_tag(multi_pod), "status": "SKIP",
                **meta}

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    n_chips = 512 if multi_pod else 256
    cfg = get_config(arch, **overrides)
    parsed = ra.analyze_hlo(hlo)
    terms = ra.roofline_terms(parsed, cost, n_chips=n_chips,
                              per_device_program=True)
    result = {
        "status": "OK",
        **meta,
        "compile_s": round(time.time() - t0, 2),
        "n_chips": n_chips,
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_per_device_gb": round(
                (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                 + mem.output_size_in_bytes
                 - mem.alias_size_in_bytes) / 2**30, 3),
        },
        "cost_analysis": {"flops": cost.get("flops", 0.0),
                          "bytes": cost.get("bytes accessed", 0.0)},
        "hlo_parsed": parsed.summary(),
        "roofline": terms,
        "model_flops": ra.model_flops(cfg, meta["kind"], meta["batch"],
                                      meta["seq_len"]),
    }
    if save_hlo:
        os.makedirs(ART_DIR, exist_ok=True)
        tag = f"{arch}_{shape}_{_mesh_tag(multi_pod)}"
        with gzip.open(os.path.join(ART_DIR, tag + ".hlo.gz"), "wt") as f:
            f.write(hlo)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="one arch (default all)")
    ap.add_argument("--shape", default=None, help="one shape (default all)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list_archs()
    shapes = [args.shape] if args.shape else list(sp.SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    os.makedirs(ART_DIR, exist_ok=True)
    results = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                r = run_cell(arch, shape, mp, save_hlo=args.save_hlo)
                results.append(r)
                tag = f"{arch}_{shape}_{_mesh_tag(mp)}"
                with open(os.path.join(ART_DIR, tag + ".json"), "w") as f:
                    json.dump(r, f, indent=1)
                status = r["status"]
                extra = ""
                if status == "OK":
                    extra = (f"mem/dev={r['memory']['peak_per_device_gb']}GB"
                             f" compile={r['compile_s']}s")
                elif status == "ERROR":
                    extra = r["error"]
                print(f"[{status:5s}] {tag} {extra}", flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
    n_err = sum(r["status"] == "ERROR" for r in results)
    print(f"\n{len(results)} cells: "
          f"{sum(r['status'] == 'OK' for r in results)} ok, "
          f"{sum(r['status'] == 'SKIP' for r in results)} skipped, "
          f"{n_err} errors")
    return 1 if n_err else 0


if __name__ == "__main__":
    # before the first backend init: jax locks the device count then
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    raise SystemExit(main())
