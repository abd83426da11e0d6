"""End-to-end training driver: data -> pjit train step -> checkpoint/restart.

Production pieces wired together: sharded step (same builders as the
dry-run), microbatch gradient accumulation, optional int8 gradient
compression on the DP all-reduce, async atomic checkpoints, watchdog
straggler detection, supervised restart, seekable data.

CLI (CPU-scale example — examples/train_lm.py wraps this):
  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --reduced \
      --steps 50 --batch 8 --seq 128
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import time
from functools import partial
from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.ckpt import Checkpointer, latest_step, restore
from repro.configs.base import ModelConfig, reduced
from repro.configs.registry import get_config
from repro.data.tokens import TokenPipeline
from repro.ft.watchdog import Watchdog, run_with_restart
from repro.launch import shardings as sh
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.models import build_model
from repro.models import layers as Lmod
from repro.obs import trace as obs_trace
from repro.optim import make_optimizer
from repro.optim.schedules import warmup_cosine

__all__ = ["TrainSettings", "train"]


@dataclasses.dataclass
class TrainSettings:
    steps: int = 50
    batch: int = 8
    seq: int = 128
    lr: float = 3e-4
    warmup: int = 10
    microbatches: int = 1  # gradient accumulation
    grad_compression: bool = False
    ckpt_dir: str = "results/ckpt"
    ckpt_every: int = 25
    keep_last: int = 3
    seed: int = 0
    log_every: int = 10


def _build_step(model, cfg: ModelConfig, st: TrainSettings, mesh):
    opt_init, opt_update = make_optimizer(cfg.optimizer)
    l2m = sh.logical_to_mesh(mesh)
    Lmod.set_act_rules(
        {
            k: (axes, int(np.prod([mesh.shape[a] for a in axes])))
            for k, axes in (("dp", l2m["dp"]), ("tp", l2m["tp"]))
        }
    )

    def train_step(params, opt_state, batch, step):
        def loss_of(p, b):
            loss, mets = model.loss_fn(p, b)
            return loss, mets

        if st.microbatches > 1:
            # gradient accumulation over sequential microbatches
            mb = jax.tree.map(
                lambda x: x.reshape(st.microbatches, -1, *x.shape[1:]), batch
            )

            def acc_fn(carry, mbi):
                g_acc, l_acc = carry
                (loss, _), g = jax.value_and_grad(loss_of, has_aux=True)(params, mbi)
                return (jax.tree.map(jnp.add, g_acc, g), l_acc + loss), None

            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, loss_sum), _ = jax.lax.scan(acc_fn, (zeros, 0.0), mb)
            grads = jax.tree.map(lambda g: g / st.microbatches, grads)
            loss = loss_sum / st.microbatches
            mets = {}
        else:
            (loss, mets), grads = jax.value_and_grad(loss_of, has_aux=True)(
                params, batch
            )
        lr_t = warmup_cosine(step, st.lr, st.warmup, st.steps)
        new_params, new_opt, opt_mets = opt_update(grads, opt_state, params, lr_t)
        return new_params, new_opt, {"loss": loss, "lr": lr_t, **mets, **opt_mets}

    return opt_init, jax.jit(train_step, donate_argnums=(0, 1))


def train(
    cfg: ModelConfig,
    st: TrainSettings,
    mesh=None,
    resume: Optional[int] = None,
    stop_at: Optional[int] = None,
) -> dict:
    """``stop_at`` simulates an interruption at that step (tests/FT drills)
    while keeping the LR schedule defined by ``st.steps``."""
    mesh = mesh or make_local_mesh()
    model = build_model(cfg)
    pipe = TokenPipeline(
        vocab=cfg.vocab, seq_len=st.seq, global_batch=st.batch, seed=st.seed
    )
    opt_init, step_fn = _build_step(model, cfg, st, mesh)

    with jax.set_mesh(mesh):
        params = model.init(jax.random.PRNGKey(st.seed))
        opt_state = opt_init(params)

        start = 0
        ck = latest_step(st.ckpt_dir) if resume is None else resume
        if ck is not None:
            params = restore(st.ckpt_dir, ck, params)
            opt_state = restore(Path(st.ckpt_dir) / "opt", ck, opt_state)
            start = ck
            print(f"[train] resumed from step {ck}")

        ckpt = Checkpointer(st.ckpt_dir, st.keep_last)
        ckpt_opt = Checkpointer(Path(st.ckpt_dir) / "opt", st.keep_last)
        wd = Watchdog(Path(st.ckpt_dir) / "heartbeat.json")
        losses = []
        step_s = []
        t0 = time.perf_counter()
        end = min(st.steps, stop_at) if stop_at is not None else st.steps
        for step in range(start, end):
            t_step = time.perf_counter()
            with obs_trace.span("train.step", step=step):
                batch = jax.tree.map(jnp.asarray, pipe.batch(step))
                params, opt_state, mets = step_fn(
                    params, opt_state, batch, jnp.asarray(step, jnp.int32)
                )
                loss = float(mets["loss"])  # waits for the step
            step_s.append(time.perf_counter() - t_step)
            losses.append(loss)
            wd.step(step, {"loss": loss})
            if step % st.log_every == 0 or step == st.steps - 1:
                print(f"[train] step {step}: loss {loss:.4f} lr {float(mets['lr']):.2e}")
            if (step + 1) % st.ckpt_every == 0 or step == end - 1:
                ckpt.save_async(step + 1, params)
                ckpt_opt.save_async(step + 1, opt_state)
        ckpt.wait()
        ckpt_opt.wait()
    return {
        "final_loss": losses[-1],
        "first_loss": losses[0],
        "losses": losses,
        "step_s": step_s,
        "wall_s": time.perf_counter() - t0,
        "params": params,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="results/ckpt")
    ap.add_argument("--max-restarts", type=int, default=2)
    ap.add_argument(
        "--obs-log",
        default=None,
        metavar="PATH",
        help="stream repro.obs spans (train.step, data.batch) to PATH as JSONL",
    )
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    st = TrainSettings(
        steps=args.steps,
        batch=args.batch,
        seq=args.seq,
        lr=args.lr,
        microbatches=args.microbatches,
        ckpt_dir=args.ckpt_dir,
    )

    def run(resume):
        out = train(cfg, st, resume=resume)
        print(
            f"[train] done: loss {out['first_loss']:.4f} -> {out['final_loss']:.4f} "
            f"in {out['wall_s']:.1f}s"
        )
        return st.steps

    with contextlib.ExitStack() as stack:
        if args.obs_log:
            stack.enter_context(obs_trace.tracing(jsonl=args.obs_log))
        run_with_restart(run, max_restarts=args.max_restarts)


if __name__ == "__main__":
    main()
