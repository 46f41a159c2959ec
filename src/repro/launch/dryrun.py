import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count at first backend init).  DRYRUN_DEVICES is a test hook for smaller
# placeholder fleets; it still runs before jax is imported.
if os.environ.get("DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["DRYRUN_DEVICES"])

"""Multi-pod dry-run: AOT-lower + compile every (arch x shape x mesh) cell.

For each cell this produces, with zero device allocation:
  * proof the distribution config is coherent (lower+compile succeeds),
  * ``memory_analysis()``  -> per-device bytes (fits in 16 GiB HBM?),
  * ``cost_analysis()``    -> HLO FLOPs / bytes accessed,
  * the post-SPMD collective schedule (parsed from ``compiled.as_text()``),
all dumped as JSON for the roofline analysis (§Roofline in EXPERIMENTS.md).

Usage:
  python -m repro.launch.dryrun --arch qwen3_1p7b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --out-dir benchmarks/results/dryrun
"""

import argparse
import json
import time
import traceback
from functools import partial

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

import repro.configs as cfgs
from repro.launch.mesh import TARGET_KIND, make_production_mesh, peaks
from repro.models import SHAPES, build, cell_applicable
from repro.optim import init_opt_state, opt_state_partition_specs
from repro.runtime.train_loop import TrainConfig, make_train_step
from repro.sharding.hints import mesh_axes
from repro.sharding import specs as sspecs
from repro.utils.hlo import analyze_hlo


def _named(mesh, spec_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def build_lowering(arch: str, shape_name: str, mesh, *,
                   train_overrides: dict | None = None,
                   batch_override: int | None = None,
                   opt_overrides: dict | None = None):
    """Returns (lowered, meta) for one cell."""
    cfg = cfgs.get(arch)
    if train_overrides:
        cfg = cfg.replace(**train_overrides)
    api = build(cfg)
    axes = mesh.axis_names
    cell = SHAPES[shape_name]
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp = 1
    for a, s in sizes.items():
        if a in ("pod", "data"):
            dp *= s

    params_sds = api.param_specs()
    # NOTE (perf iteration, refuted): a "serve" rule set sharding weight
    # contract dims over (data x model) — no per-token FSDP gathers — was
    # measured 3-70x WORSE on the decode cells (GSPMD re-shards the
    # activations/caches around every projection instead).  The train
    # layout + seq-parallel flash-decode stands.  See EXPERIMENTS.md §Perf.
    pspecs = sspecs.tree_partition_specs(params_sds, axes, axis_sizes=sizes,
                                         mode="train")
    batch_sds = api.input_specs(shape_name, batch_override=batch_override)
    bspecs = sspecs.batch_partition_specs(batch_sds, axes, axis_sizes=sizes)

    if cell.kind == "train":
        tc = TrainConfig(**(opt_overrides or {}))
        step = make_train_step(api, tc, axes=axes)
        opt_sds = jax.eval_shape(
            partial(init_opt_state, moment_dtype=cfg.opt_state_dtype,
                    master_fp32=tc.master_fp32), params_sds)
        ospecs = opt_state_partition_specs(opt_sds, pspecs, axes,
                                           axis_sizes=sizes)
        state_sds = {"params": params_sds, "opt": opt_sds}
        state_specs = {"params": pspecs, "opt": ospecs}
        with mesh:
            jitted = jax.jit(
                step,
                in_shardings=(_named(mesh, state_specs), _named(mesh, bspecs)),
                out_shardings=(_named(mesh, state_specs), None),
                donate_argnums=0,  # new state aliases old: halves resident state
            )
            lowered = jitted.lower(state_sds, batch_sds)
        return lowered, {"kind": "train", "cfg": cfg}

    if cell.kind == "prefill":
        def prefill_fn(params, batch):
            with mesh_axes(axes):
                return api.prefill(params, batch)

        with mesh:
            jitted = jax.jit(
                prefill_fn,
                in_shardings=(_named(mesh, pspecs), _named(mesh, bspecs)),
            )
            lowered = jitted.lower(params_sds, batch_sds)
        return lowered, {"kind": "prefill", "cfg": cfg}

    # decode
    long_ctx = shape_name.startswith("long")
    cache_sds = api.cache_specs(shape_name, batch_override=batch_override)
    B = batch_override or cell.global_batch
    cspecs = sspecs.cache_partition_specs(cache_sds, axes, global_batch=B,
                                          dp_size=dp, axis_sizes=sizes)

    def decode_fn(params, batch, caches):
        with mesh_axes(axes):
            return api.decode(params, batch, caches, long_context=long_ctx)

    with mesh:
        jitted = jax.jit(
            decode_fn,
            in_shardings=(_named(mesh, pspecs), _named(mesh, bspecs),
                          _named(mesh, cspecs)),
            out_shardings=(None, _named(mesh, cspecs)),
        )
        lowered = jitted.lower(params_sds, batch_sds, cache_sds)
    return lowered, {"kind": "decode", "cfg": cfg}


def analyze(lowered, *, mesh, want_hlo: bool = False) -> dict:
    t0 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t0
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    ana = analyze_hlo(hlo)  # trip-count-weighted (cost_analysis counts
    coll = ana.collectives  # while bodies once)
    n_chips = mesh.devices.size
    out = {
        "n_chips": int(n_chips),
        "mesh_shape": list(mesh.devices.shape),
        "mesh_axes": list(mesh.axis_names),
        "compile_s": compile_s,
        "memory": {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes),
            "peak_bytes_per_device": int(
                mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes - mem.alias_size_in_bytes),
            "hbm_bytes_per_device": int(peaks(TARGET_KIND)["hbm_bytes"]),
        },
        # cost_analysis on the post-SPMD module is PER DEVICE and counts
        # while bodies ONCE (under-reports scanned models); the hlo_*
        # numbers are trip-count weighted re-derivations from the HLO text.
        "flops_per_device_raw": float(cost.get("flops", 0.0)),
        "bytes_accessed_per_device_raw": float(cost.get("bytes accessed", 0.0)),
        "hlo_dot_flops_per_device": ana.dot_flops,
        "hlo_bytes_accessed_per_device": ana.bytes_accessed,
        "collectives": coll.as_dict(),
    }
    if want_hlo:
        out["hlo_text"] = hlo
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             train_overrides: dict | None = None, **kw) -> dict:
    cfg = cfgs.get(arch)
    ok, reason = cell_applicable(cfg, shape_name)
    mesh_name = "multi" if multi_pod else "single"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name}
    if not ok:
        rec["status"] = "skipped"
        rec["reason"] = reason
        return rec
    if SHAPES[shape_name].kind == "train":
        # full block remat is the production policy at 4k x 256 batch
        train_overrides = {"remat": True, **(train_overrides or {})}
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    lowered, meta = build_lowering(arch, shape_name, mesh,
                                   train_overrides=train_overrides, **kw)
    rec["lower_s"] = time.time() - t0
    rec.update(analyze(lowered, mesh=mesh))
    rec["status"] = "ok"
    rec["kind"] = meta["kind"]
    total, active = meta["cfg"].param_counts()
    rec["params_total"] = total
    rec["params_active"] = active
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None,
                    choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out-dir", type=str,
                    default="benchmarks/results/dryrun")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    archs = cfgs.ARCH_IDS if (args.all or args.arch is None) else [
        cfgs.canonical(args.arch)]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    os.makedirs(args.out_dir, exist_ok=True)
    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                name = f"{arch}__{shape}__{'multi' if mp else 'single'}"
                path = os.path.join(args.out_dir, name + ".json")
                if os.path.exists(path) and not args.force:
                    print(f"[skip cached] {name}")
                    continue
                print(f"[dryrun] {name} ...", flush=True)
                try:
                    rec = run_cell(arch, shape, mp)
                except Exception as e:
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "multi" if mp else "single",
                           "status": "error", "error": repr(e),
                           "traceback": traceback.format_exc()}
                    failures += 1
                    print(f"  ERROR: {e}")
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                if rec.get("status") == "ok":
                    mem = rec["memory"]["peak_bytes_per_device"] / 2**30
                    print(f"  ok: lower {rec['lower_s']:.1f}s compile "
                          f"{rec['compile_s']:.1f}s mem/dev {mem:.2f} GiB "
                          f"collectives {rec['collectives']['count']}")
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()
