import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape) cell on the
production meshes, record memory/cost analysis + roofline terms.

The two lines above MUST stay first: jax locks the device count on first
init, and the dry-run needs 512 placeholder host devices for the 2×16×16
multi-pod mesh. (Smoke tests / benches import other modules and see 1 device.)

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-135m --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out results/dryrun]

Results are cached per (arch, shape, mesh) in JSON; re-runs skip green cells.
"""

import argparse
import json
import time
import traceback
from pathlib import Path

import jax

from repro.configs.registry import ARCHS, for_shape, get_config
from repro.configs.shapes import SHAPES, valid_cells
from repro.launch import shardings as shmod
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import build_cell
from repro.roofline import hw
from repro.roofline.analysis import roofline

DEFAULT_OUT = Path("results/dryrun")


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: Path, force=False):
    mesh_tag = "multipod" if multi_pod else "singlepod"
    out_file = out_dir / f"{arch}__{shape_name}__{mesh_tag}.json"
    if out_file.exists() and not force:
        rec = json.loads(out_file.read_text())
        if rec.get("status") == "ok":
            print(f"[cache] {arch} × {shape_name} × {mesh_tag}: ok")
            return rec

    t0 = time.time()
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_tag}
    try:
        # scope fallback recording to THIS cell: concurrent/repeated cells no
        # longer leak replication records into each other's reports
        with shmod.record_fallbacks() as cell_fallbacks:
            mesh = make_production_mesh(multi_pod=multi_pod)
            n_dev = mesh.devices.size
            cell = build_cell(arch, shape_name, mesh)
            with jax.set_mesh(mesh):
                jitted = jax.jit(
                    cell.fn,
                    in_shardings=cell.in_shardings,
                    donate_argnums=cell.donate,
                )
                lowered = jitted.lower(*cell.args)
                t_lower = time.time() - t0
                compiled = lowered.compile()
                t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        mem_stats = {}
        for k in ("argument_size_in_bytes", "output_size_in_bytes", "temp_size_in_bytes"):
            v = getattr(mem, k, None)
            if v is not None:
                mem_stats[k] = int(v)
        # resident bytes per device: sharded argument shards (weights, opt
        # state, caches, batch). CPU temp sizes are unfused-buffer artifacts,
        # reported but not representative of TPU HBM with remat.
        import numpy as np

        resident = 0
        shard_leaves = jax.tree.leaves(
            cell.in_shardings, is_leaf=lambda x: hasattr(x, "shard_shape")
        )
        for sds, shd in zip(jax.tree.leaves(cell.args), shard_leaves):
            shard = shd.shard_shape(sds.shape) if hasattr(shd, "shard_shape") else sds.shape
            resident += int(np.prod(shard)) * sds.dtype.itemsize
        mem_stats["bytes"] = resident

        cost_list = compiled.cost_analysis()
        cost = cost_list[0] if isinstance(cost_list, (list, tuple)) else cost_list
        hlo = compiled.as_text()

        rep = roofline(
            arch, SHAPES[shape_name], cell.cfg, cost, hlo, n_dev, mem_stats,
            device_kind=hw.V5E,
        )
        rec.update(
            status="ok",
            n_devices=n_dev,
            lower_s=round(t_lower, 1),
            compile_s=round(t_compile, 1),
            memory=mem_stats,
            fallbacks=list(cell_fallbacks),
            roofline=rep.to_dict(),
            roofline_fraction=rep.roofline_fraction,
            hlo_bytes=len(hlo),
        )
        print(
            f"[ok] {arch} × {shape_name} × {mesh_tag}: "
            f"compile {t_compile:.0f}s, mem/dev {resident/2**30:.2f} GiB, "
            f"t=(c {rep.t_compute*1e3:.2f} | m {rep.t_memory*1e3:.2f} | "
            f"x {rep.t_collective*1e3:.2f}) ms, bottleneck={rep.bottleneck}, "
            f"MODEL/HLO={rep.useful_ratio:.2f}"
        )
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        rec.update(status="fail", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
        print(f"[FAIL] {arch} × {shape_name} × {mesh_tag}: {e}")

    out_dir.mkdir(parents=True, exist_ok=True)
    out_file.write_text(json.dumps(rec, indent=2, default=str))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default=str(DEFAULT_OUT))
    args = ap.parse_args()
    out_dir = Path(args.out)

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    cells = []
    if args.all:
        for arch, cfg in ARCHS.items():
            for shp in valid_cells(cfg):
                cells.append((arch, shp))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    n_ok = n_fail = 0
    for multi in meshes:
        for arch, shp in cells:
            rec = run_cell(arch, shp, multi, out_dir, force=args.force)
            if rec.get("status") == "ok":
                n_ok += 1
            else:
                n_fail += 1
    print(f"\ndry-run complete: {n_ok} ok, {n_fail} failed")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
