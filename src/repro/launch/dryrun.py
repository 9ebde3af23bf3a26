import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this builds abstract params/optimizer/batch/cache
(ShapeDtypeStructs — nothing allocates), jits the train/prefill/serve step
with the production shardings, and runs ``.lower().compile()``.  Success
proves the distribution config is coherent; ``memory_analysis()`` proves it
fits; ``cost_analysis()`` + the collective bytes parsed from the HLO feed
§Roofline.

Usage::

    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-2b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --arch all          # every cell
    PYTHONPATH=src python -m repro.launch.dryrun ... --multi-pod     # 512 chips

Writes one JSON per cell under reports/dryrun/.
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from jax import set_mesh
from repro.configs.base import SHAPES, cells_for
from repro.configs.registry import ARCH_IDS, get_config
from repro.launch.mesh import dp_axes_of, make_production_mesh
from repro.launch.sharding import (
    ShardingOptions,
    batch_shardings,
    cache_shardings,
    default_options,
    make_policy,
    param_shardings,
)
from repro.models import model
from repro.models.modules import Policy
from repro.roofline.analysis import roofline_terms
from repro.roofline.hlo_parse import analyze
from repro.train.optimizer import OptConfig, OptState, init_opt
from repro.train.train_step import make_train_step

REPORT_DIR = "reports/dryrun"


def _opt_shardings(opt_abstract: OptState, pshard):
    return OptState(
        step=jax.tree.map(lambda _: jax.sharding.NamedSharding(pshard_mesh(pshard), jax.sharding.PartitionSpec()), opt_abstract.step),
        m=pshard,
        v=pshard,
    )


def pshard_mesh(pshard):
    return jax.tree.leaves(pshard)[0].mesh


def bytes_per_device(abstract_tree, shard_tree) -> int:
    """Exact per-device resident bytes of a sharded pytree."""
    total = 0
    for leaf, sh in zip(jax.tree.leaves(abstract_tree), jax.tree.leaves(shard_tree)):
        n = int(np.prod(leaf.shape)) if leaf.shape else 1
        div = 1
        for ax, dim in zip(tuple(sh.spec) + (None,) * leaf.ndim, leaf.shape):
            if ax is None:
                continue
            size = int(np.prod([sh.mesh.shape[a] for a in (ax if isinstance(ax, tuple) else (ax,))]))
            if dim % size == 0:
                div *= size
        total += n * leaf.dtype.itemsize // div
    return total


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool, opts: ShardingOptions | None = None):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    opts = opts or default_options(cfg)
    pol = make_policy(cfg, mesh, shape.kind, opts)

    params_abs = model.abstract_params(cfg, pol)
    pshard = param_shardings(params_abs, mesh, opts, decode=shape.kind == "decode")

    state_bytes = bytes_per_device(params_abs, pshard)
    with set_mesh(mesh):
        batch_axes = tuple(mesh.axis_names) if opts.pure_dp else None
        if shape.kind == "train":
            batch_abs = model.input_specs(cfg, shape, pol)
            bshard = batch_shardings(batch_abs, mesh, batch_axes)
            opt_cfg = OptConfig(moment_dtype=opts.moment_dtype)
            opt_abs = jax.eval_shape(lambda p: init_opt(p, opt_cfg), params_abs)
            oshard = OptState(
                step=jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
                m=pshard, v=pshard,
            )
            state_bytes += 2 * bytes_per_device(opt_abs.m, pshard)
            step = make_train_step(cfg, pol, opt_cfg)
            jitted = jax.jit(
                step,
                in_shardings=(pshard, oshard, bshard),
                out_shardings=(pshard, oshard, None),
            )
            lowered = jitted.lower(params_abs, opt_abs, batch_abs)
        elif shape.kind == "prefill":
            batch_abs = model.input_specs(cfg, shape, pol)
            bshard = batch_shardings(batch_abs, mesh, batch_axes)
            fn = lambda p, b: model.prefill(p, b, cfg, pol, max_len=shape.seq_len)
            jitted = jax.jit(fn, in_shardings=(pshard, bshard))
            lowered = jitted.lower(params_abs, batch_abs)
        else:  # decode
            cache_abs, tok_abs = model.decode_input_specs(cfg, shape, pol)
            cshard = cache_shardings(cache_abs, mesh, shape.global_batch)
            state_bytes += bytes_per_device(cache_abs, cshard)
            tshard = jax.sharding.NamedSharding(
                mesh,
                jax.sharding.PartitionSpec(
                    dp_axes_of(mesh) if shape.global_batch % np.prod(
                        [mesh.shape[a] for a in dp_axes_of(mesh)]) == 0 else None,
                    None,
                ),
            )
            fn = lambda p, c, t: model.decode_step(p, c, t, cfg, pol)
            jitted = jax.jit(fn, in_shardings=(pshard, cshard, tshard),
                             out_shardings=(None, cshard))
            lowered = jitted.lower(params_abs, cache_abs, tok_abs)
    return cfg, mesh, lowered, state_bytes


def run_cell(arch: str, shape_name: str, *, multi_pod: bool, save_hlo: bool = False,
             opts: ShardingOptions | None = None, tag: str = "") -> dict:
    t0 = time.time()
    n_chips = 512 if multi_pod else 256
    rec = {"arch": arch, "shape": shape_name, "mesh": "2x16x16" if multi_pod else "16x16",
           "chips": n_chips, "status": "error", "tag": tag}
    try:
        cfg, mesh, lowered, state_bytes = lower_cell(arch, shape_name, multi_pod=multi_pod, opts=opts)
        rec["state_bytes_per_device"] = int(state_bytes)
        rec["fits_16gb_hbm"] = bool(state_bytes < 15.5 * 2**30)
        rec["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 1)
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        rec["memory"] = {
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "peak_bytes": int(getattr(mem, "peak_memory_in_bytes", 0) or 0),
        }
        rec["cost_analysis_raw"] = {  # loops counted once — reference only
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        }
        hlo_text = compiled.as_text()
        hlo = analyze(hlo_text)  # loop-aware, per-device
        rec["hlo"] = {k: (v if not isinstance(v, dict) else v) for k, v in hlo.items()}
        rec["roofline"] = roofline_terms(
            flops_dev=hlo["flops"],
            hbm_dev=hlo["hbm_bytes"],
            hbm_dev_fused=hlo["hbm_bytes_fused"],
            coll_dev=sum(hlo["collective_bytes"].values()),
        )
        shape = SHAPES[shape_name]
        tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
        mult = 6 if shape.kind == "train" else 2
        model_flops = mult * cfg.param_count(active_only=True) * tokens
        rec["model_flops_dev"] = float(model_flops / n_chips)
        rec["useful_ratio"] = float(model_flops / n_chips / max(hlo["flops"], 1.0))
        rec["status"] = "ok"
        if save_hlo:
            os.makedirs(REPORT_DIR, exist_ok=True)
            with open(os.path.join(REPORT_DIR, f"{arch}__{shape_name}__{rec['mesh']}{tag}.hlo"), "w") as f:
                f.write(hlo_text)
    except Exception as e:  # noqa: BLE001 — report and continue
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["total_s"] = round(time.time() - t0, 1)
    os.makedirs(REPORT_DIR, exist_ok=True)
    out = os.path.join(REPORT_DIR, f"{arch}__{shape_name}__{rec['mesh']}{tag}.json")
    with open(out, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, help="arch id or 'all'")
    ap.add_argument("--shape", default="all", help="shape name or 'all'")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--save-hlo", action="store_true")
    args = ap.parse_args()

    archs = list(ARCH_IDS) if args.arch == "all" else [args.arch]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for arch in archs:
        cfg = get_config(arch)
        shapes = cells_for(cfg) if args.shape == "all" else [args.shape]
        for shape in shapes:
            for mp in meshes:
                rec = run_cell(arch, shape, multi_pod=mp, save_hlo=args.save_hlo)
                status = rec["status"]
                extra = ("" if status == "ok" else " :: " + rec.get("error", ""))
                print(f"[{status}] {arch} x {shape} x {rec['mesh']} "
                      f"({rec['total_s']}s){extra}", flush=True)
                if status == "ok":
                    m = rec["memory"]
                    per_dev = (m["argument_bytes"] + m["temp_bytes"])
                    r = rec["roofline"]
                    print(f"    mem/device ~{per_dev/2**30:.2f} GiB  "
                          f"flops/dev {rec['hlo']['flops']:.3e}  useful {rec['useful_ratio']:.2f}  "
                          f"terms c={r['compute_s']:.3f}s m={r['memory_s']:.3f}s "
                          f"x={r['collective_s']:.3f}s -> {r['bottleneck']}", flush=True)


if __name__ == "__main__":
    main()
