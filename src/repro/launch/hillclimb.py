import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Perf hillclimb driver: re-lower + re-analyse named variants of the three
chosen cells (EXPERIMENTS.md §Perf). Baselines live in results/dryrun.

  PYTHONPATH=src python -m repro.launch.hillclimb [--variant NAME]
"""

import argparse
import dataclasses
import json
import time
from pathlib import Path

import jax

from repro.configs.registry import for_shape, get_config
from repro.configs.shapes import SHAPES
from repro.core.cim_linear import CiMConfig
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import build_cell
from repro.roofline import hw
from repro.roofline.analysis import roofline

OUT = Path("results/hillclimb")


def _cfg(arch, shape, **over):
    cfg = for_shape(get_config(arch), SHAPES[shape])
    return dataclasses.replace(cfg, **over) if over else cfg


# variant name -> (arch, shape, cfg_override or None)
VARIANTS = {
    # Cell A: llama3-405b train_4k — memory-bound (mixed-precision materialization)
    # A1+A2 live in the model code (rms_norm + bf16 attention scores); this
    # re-lowers the same config against the updated implementation.
    "A_llama405b_train/opt_mixed_precision": ("llama3-405b", "train_4k", {}),
    # A3: smaller attention KV chunk — fewer bytes per materialized score tile
    "A_llama405b_train/opt_chunk512": (
        "llama3-405b",
        "train_4k",
        {"attn_chunk": 512},
    ),
    # Cell B: qwen3-moe train_4k — collective-bound (dispatch elimination)
    "B_qwen3moe_train/opt_dense_moe": (
        "qwen3-moe-30b-a3b",
        "train_4k",
        {"moe_impl": "dense"},
    ),
    # B2: dense MoE + mixed precision together on the runner-up (moonshot)
    "B_moonshot_train/opt_dense_moe": (
        "moonshot-v1-16b-a3b",
        "train_4k",
        {"moe_impl": "dense"},
    ),
    # Cell C: command-r-plus decode_32k — memory-bound serving
    # C1: int8 weight/activation dots (the paper's low-precision product-sums on MXU)
    "C_commandr_decode/opt_int8_weights": (
        "command-r-plus-104b",
        "decode_32k",
        {"cim": CiMConfig(mode="int8_dot", ste=False)},
    ),
    # C2: + int8 KV cache
    "C_commandr_decode/opt_int8_weights_kv": (
        "command-r-plus-104b",
        "decode_32k",
        {"cim": CiMConfig(mode="int8_dot", ste=False), "kv_quant_int8": True},
    ),
    # C2b: int8 KV cache alone (ablation)
    "C_commandr_decode/opt_int8_kv_only": (
        "command-r-plus-104b",
        "decode_32k",
        {"kv_quant_int8": True},
    ),
}


def run_variant(name: str, force: bool = False):
    arch, shape_name, over = VARIANTS[name]
    out_file = OUT / (name.replace("/", "__") + ".json")
    if out_file.exists() and not force:
        rec = json.loads(out_file.read_text())
        if rec.get("status") == "ok":
            print(f"[cache] {name}")
            return rec
    t0 = time.time()
    rec = {"variant": name, "arch": arch, "shape": shape_name}
    try:
        mesh = make_production_mesh()
        cfg = _cfg(arch, shape_name, **over)
        cell = build_cell(arch, shape_name, mesh, cfg_override=cfg)
        with jax.set_mesh(mesh):
            compiled = (
                jax.jit(cell.fn, in_shardings=cell.in_shardings, donate_argnums=cell.donate)
                .lower(*cell.args)
                .compile()
            )
        import numpy as np

        resident = 0
        shard_leaves = jax.tree.leaves(
            cell.in_shardings, is_leaf=lambda x: hasattr(x, "shard_shape")
        )
        for sds, shd in zip(jax.tree.leaves(cell.args), shard_leaves):
            shard = shd.shard_shape(sds.shape) if hasattr(shd, "shard_shape") else sds.shape
            resident += int(np.prod(shard)) * sds.dtype.itemsize

        rep = roofline(
            arch, SHAPES[shape_name], cell.cfg, {}, compiled.as_text(),
            mesh.devices.size, {"bytes": resident}, device_kind=hw.V5E,
        )
        rec.update(
            status="ok",
            compile_s=round(time.time() - t0, 1),
            memory={"bytes": resident},
            roofline=rep.to_dict(),
            roofline_fraction=rep.roofline_fraction,
        )
        print(
            f"[ok] {name}: t=(c {rep.t_compute:.2f} | m {rep.t_memory:.2f} | "
            f"x {rep.t_collective:.2f}) s, mem/dev {resident/2**30:.2f} GiB, "
            f"bottleneck={rep.bottleneck}, frac={rep.roofline_fraction:.4f}"
        )
    except Exception as e:  # noqa: BLE001
        import traceback

        rec.update(status="fail", error=str(e), traceback=traceback.format_exc()[-3000:])
        print(f"[FAIL] {name}: {e}")
    OUT.mkdir(parents=True, exist_ok=True)
    out_file.write_text(json.dumps(rec, indent=2, default=str))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--variant", default=None)
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()
    names = [args.variant] if args.variant else list(VARIANTS)
    for n in names:
        run_variant(n, force=args.force)


if __name__ == "__main__":
    main()
