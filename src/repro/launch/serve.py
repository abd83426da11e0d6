"""Batched serving driver: continuous prefill + decode over a request queue.

Requests arrive with different prompt lengths; the driver pads each to the
cache size, runs one batched prefill, then steps decode for all sequences in
lock-step (static batch, the classic TPU serving layout). Supports the
paper's CiM-quantized inference mode (--cim fake_quant) — the technique as a
deployable serving feature.

CLI (CPU-scale): examples/serve_lm.py wraps this.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import itertools
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, reduced
from repro.configs.registry import get_config
from repro.core.cim_linear import CiMConfig
from repro.launch.compile_cache import use_compile_cache
from repro.models import build_model
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

__all__ = ["ServeSettings", "serve_batch", "parse_fabric_mesh", "compiled_model"]

# each served batch's id, shared by its spans
_BATCH_IDS = itertools.count()


@functools.lru_cache(maxsize=8)
def compiled_model(cfg: ModelConfig, seed: int):
    """Build + initialize ``cfg`` and wrap its prefill/decode in ``jax.jit``
    ONCE per ``(cfg, seed)``.

    ``serve_batch`` used to rebuild the model and re-wrap ``jax.jit`` on
    every call, which discarded the trace cache and re-traced (and
    re-compiled) prefill and decode each time; hoisting the wrappers here
    makes repeated ``serve_batch`` calls — the continuous-batching serving
    loop — reuse the compiled executables. ``ModelConfig`` is a frozen
    dataclass, so it keys the LRU directly.

    Returns ``(model, params, jit_prefill, jit_decode)``.
    """
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    return model, params, jax.jit(model.prefill), jax.jit(model.decode_step)


def parse_fabric_mesh(spec: str) -> tuple:
    """Parse a ``--fabric-mesh`` ``DxM`` spec (e.g. ``2x4``) into
    ``(data, model)`` and validate it against
    ``repro.launch.mesh.make_chip_mesh`` — the same axis rules the shard
    planner uses, so a spec that parses here is a mesh the planner accepts.

    Example::

        >>> parse_fabric_mesh("2x4")
        (2, 4)
    """
    parts = spec.lower().replace(" ", "").split("x")
    if len(parts) != 2:
        raise ValueError(f"--fabric-mesh wants DxM (e.g. 2x4), got {spec!r}")
    try:
        data, model = int(parts[0]), int(parts[1])
    except ValueError:
        raise ValueError(f"--fabric-mesh wants integer axes, got {spec!r}") from None
    from repro.launch.mesh import make_chip_mesh

    make_chip_mesh(data, model)  # raises on axes < 1; abstract fallback is fine
    return data, model


@dataclasses.dataclass
class ServeSettings:
    batch: int = 4
    prompt_len: int = 32
    gen_len: int = 32
    seed: int = 0
    greedy: bool = True


def serve_batch(
    cfg: ModelConfig,
    st: ServeSettings,
    prompts: Optional[np.ndarray] = None,
    fabric_rollup: Optional[dict] = None,
):
    """Serve one static batch: returns dict with tokens + timing.

    ``fabric_rollup`` (a ``fabric_report`` / ``sharded_fabric_report`` dict
    for ONE forward pass) turns the batching log line into a per-request cost
    model: estimated CiM latency / energy / EMA per request are printed with
    the batch and folded into the returned dict — the first step of
    fabric-aware batching decisions (ROADMAP).

    With ``repro.obs`` metrics collection active (serve CLI:
    ``--obs-metrics``) the batching log line is replaced by the per-request
    observability summary — fused/fallback request counters, conversion and
    link-bit totals, and the measured-vs-modeled link latency with the named
    ``link_clock_calibration`` constant — read back from the live registry.
    """
    model, params, prefill, decode = compiled_model(cfg, st.seed)
    rng = np.random.default_rng(st.seed)
    if prompts is None:
        prompts = rng.integers(0, cfg.vocab, (st.batch, st.prompt_len)).astype(np.int32)
    b, s = prompts.shape
    total = s + st.gen_len
    batch_id = next(_BATCH_IDS)

    t0 = time.perf_counter()
    with obs_trace.span("serve.prefill", batch=batch_id, rows=b, prompt_len=s):
        with obs_trace.span("serve.make_cache", batch=batch_id):
            cache = model.make_cache(b, total)
        logits, cache = prefill(params, jnp.asarray(prompts), cache)
        finite = jnp.isfinite(logits).all()
        next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        jax.block_until_ready(next_tok)
    t_prefill = time.perf_counter() - t0

    out_tokens = [next_tok]
    t0 = time.perf_counter()
    with obs_trace.span("serve.decode", batch=batch_id, rows=b, gen_len=st.gen_len):
        for i in range(st.gen_len - 1):
            with obs_trace.span("serve.decode_step", batch=batch_id, step=i):
                pos = jnp.asarray(s + i, jnp.int32)
                logits, cache = decode(params, next_tok, pos, cache)
                finite = finite & jnp.isfinite(logits).all()
                next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
                out_tokens.append(next_tok)
        jax.block_until_ready(next_tok)
    t_decode = time.perf_counter() - t0

    obs_metrics.inc("serve_requests_total", b, help="Requests served (batch slots).")
    obs_metrics.observe(
        "serve_prefill_seconds", t_prefill, help="Batched prefill wall time."
    )
    obs_metrics.observe(
        "serve_decode_seconds", t_decode, help="Batched decode wall time."
    )

    with obs_trace.span("serve.fetch", batch=batch_id):
        gen = np.stack([np.asarray(t) for t in out_tokens], axis=1)
    out = {
        "prompts": prompts,
        "generated": gen,
        "prefill_s": t_prefill,
        "decode_s": t_decode,
        "decode_tok_s": b * (st.gen_len - 1) / max(t_decode, 1e-9),
        "logits_finite": bool(finite),
    }
    if fabric_rollup is not None:
        t = fabric_rollup["totals"]
        # the rollup maps one batched forward pass (tokens = batch); prefill
        # runs s token positions, decode gen_len - 1 more, so a request costs
        # (s + gen_len - 1) passes shared across the b requests of the batch
        passes = (s + st.gen_len - 1) / b
        xchip_bits = t.get("crosschip_bits_per_pass", 0)
        # mesh rollups carry the double-buffered round-overlap latency
        # (reduce-scatter of layer i hidden under layer i+1's conversions)
        latency_s = t.get("latency_s_overlapped", t["latency_s"])
        fab = {
            "latency_s_per_request": latency_s * passes,
            "energy_uj_per_request": (
                t["digitization_energy_pj"]
                + t["ema_energy_pj"]
                + t.get("crosschip_energy_pj", 0.0)
            )
            * passes
            / 1e6,
            "onchip_ema_bits_per_request": t["ema_bits_per_pass"] * passes,
            "crosschip_bits_per_request": xchip_bits * passes,
            "model_resident": t["model_resident"],
            "n_chips": fabric_rollup.get("mesh", {}).get("n_chips", 1),
            "exec_backend": fabric_rollup.get("exec_backend", "n/a"),
        }
        out["fabric"] = fab
        if obs_metrics.active():
            # the per-request observability summary line: live counters from
            # the registry (fed by the fabric layers + the validation pass)
            # replace the static cost-model printout
            obs_metrics.inc(
                "fabric_ema_bits_total",
                fab["onchip_ema_bits_per_request"] * b,
                help="On-chip external-memory-access bits for requests served.",
            )
            fused = obs_metrics.get_value("fabric_requests_total", path="fused")
            fell = obs_metrics.get_value("fabric_requests_total", path="fallback")
            conv = obs_metrics.get_value("fabric_conversions_total")
            direct = obs_metrics.get_value("fabric_direct_conversions_total")
            bits = obs_metrics.get_value("fabric_link_bits_total")
            modeled = obs_metrics.get_value("fabric_modeled_link_seconds")
            measured = obs_metrics.get_value("fabric_measured_collective_seconds")
            calib = obs_metrics.get_value("fabric_link_clock_calibration")
            obs_trace.event(
                "serve.request_summary", batch=batch_id, rows=b, total_tokens=total,
                fused_requests=fused, fallback_requests=fell,
                conversions=conv, direct_conversions=direct, link_bits=bits,
                modeled_link_s=modeled, measured_collective_s=measured,
                link_clock_calibration=calib,
            )
            print(
                f"[serve] obs batch {b}x{total} tok on {fab['n_chips']} chip(s) "
                f"[{fab['exec_backend']}]: fused {fused:.0f} / fallback "
                f"{fell:.0f} requests; {conv:.3g} conversions "
                f"({direct:.3g} direct), "
                f"{bits:.3g} link bits; link modeled {modeled:.3g} s vs "
                f"measured {measured:.3g} s "
                f"(link_clock_calibration {calib:.3g}); est. "
                f"{fab['latency_s_per_request']*1e3:.3g} ms, "
                f"{fab['energy_uj_per_request']:.3g} uJ per request"
            )
        else:
            print(
                f"[serve] batch {b}x{total} tok on {fab['n_chips']} chip(s) "
                f"[{fab['exec_backend']}]: est. "
                f"{fab['latency_s_per_request']*1e3:.3g} ms, "
                f"{fab['energy_uj_per_request']:.3g} uJ per request "
                f"(on-chip EMA {fab['onchip_ema_bits_per_request']:.3g} bits, "
                f"cross-chip {fab['crosschip_bits_per_request']:.3g} bits, "
                f"{'resident' if fab['model_resident'] else 'reloading'})"
            )
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--cim", default=None, choices=[None, "fake_quant", "bitplane"])
    ap.add_argument(
        "--fabric",
        default=None,
        choices=[None, "pair_sar", "flash", "hybrid"],
        help="also map the model onto a chip-level CiM fabric and print the "
        "area/energy/latency/EMA rollup (repro.fabric)",
    )
    ap.add_argument("--fabric-arrays", type=int, default=256)
    ap.add_argument(
        "--fabric-chips",
        type=int,
        default=1,
        choices=[1, 4, 16],
        help="square-mesh sugar for --fabric-mesh (1 -> 1x1, 4 -> 2x2, "
        "16 -> 4x4; repro.fabric.shard)",
    )
    ap.add_argument(
        "--fabric-mesh",
        default=None,
        metavar="DxM",
        help="explicit (data x model) chip mesh, e.g. 2x4 — any axes "
        "repro.launch.mesh.make_chip_mesh accepts; overrides the "
        "--fabric-chips sugar (passing both is an error)",
    )
    ap.add_argument(
        "--fabric-backend",
        default="auto",
        choices=["auto", "sequential", "shard_map"],
        help="chip execution backend for the fabric validation pass: "
        "sequential host loop, real multi-device shard_map, or auto "
        "(shard_map when the host has the devices; repro.fabric.resolve_backend)",
    )
    ap.add_argument(
        "--fabric-program",
        action="store_true",
        help="run the whole-model fused shard_map forward "
        "(repro.fabric.compile_forward, one block chain) as the validation "
        "pass and report measured-vs-modeled link latency",
    )
    ap.add_argument(
        "--fabric-scan",
        action="store_true",
        help="compile the --fabric-program graph validation pass with "
        "scan_layers=True (repro.fabric.compile_graph_forward): the FULL "
        "model's repeated block traces once and runs under lax.scan — "
        "depth-constant compile time for deep registry configs "
        "(dense/moe families only)",
    )
    ap.add_argument(
        "--fabric-autotune",
        action="store_true",
        help="pick the (data x model) mesh and batch-bucket boundaries from "
        "the graph cost model (repro.fabric.autotune) for a synthetic "
        "ragged request mix, then validate a ragged batch through the "
        "bucketed fused-program cache (bit-exact to the per-node "
        "reference after pad-slicing)",
    )
    ap.add_argument(
        "--obs-log",
        default=None,
        metavar="PATH",
        help="stream repro.obs spans/events (fabric fallbacks, serve "
        "prefill/decode, request summaries) to PATH as JSONL",
    )
    ap.add_argument(
        "--obs-metrics",
        action="store_true",
        help="collect repro.obs metrics for the whole run: the batching log "
        "becomes the per-request obs summary line and the Prometheus text "
        "exposition prints at exit",
    )
    ap.add_argument(
        "--obs-metrics-out",
        default=None,
        metavar="PATH",
        help="write the Prometheus exposition to PATH instead of stdout "
        "(implies --obs-metrics)",
    )
    args = ap.parse_args()
    use_compile_cache()

    with contextlib.ExitStack() as stack:
        if args.obs_log:
            stack.enter_context(obs_trace.tracing(jsonl=args.obs_log))
        reg = None
        if args.obs_metrics or args.obs_metrics_out:
            reg = stack.enter_context(obs_metrics.collecting())
        _serve_main(args, ap)
        if args.obs_log:
            print(f"[serve] obs JSONL event log: {args.obs_log}")
        if reg is not None:
            if args.obs_metrics_out:
                from repro.obs.sinks import write_prometheus

                write_prometheus(reg, args.obs_metrics_out)
                print(f"[serve] obs metrics exposition: {args.obs_metrics_out}")
            else:
                print("\n[serve] obs metrics exposition:")
                print(reg.prometheus_text(), end="")


def _serve_main(args, ap):
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if args.cim:
        import dataclasses as dc

        cfg = dc.replace(cfg, cim=CiMConfig(mode=args.cim, ste=False))
    st = ServeSettings(batch=args.batch, prompt_len=args.prompt_len, gen_len=args.gen_len)

    if (
        args.fabric_chips > 1 or args.fabric_mesh or args.fabric_program
        or args.fabric_autotune
    ) and not args.fabric:
        ap.error(
            "--fabric-chips/--fabric-mesh/--fabric-program/--fabric-autotune "
            "require --fabric"
        )
    if args.fabric_autotune and cfg.family not in ("dense", "moe"):
        ap.error(
            f"--fabric-autotune needs a matmul-graph family (dense/moe); "
            f"{args.arch} is {cfg.family!r}"
        )
    if args.fabric_scan and not args.fabric_program:
        ap.error("--fabric-scan requires --fabric-program")
    if args.fabric_scan and cfg.family not in ("dense", "moe"):
        ap.error(
            f"--fabric-scan needs a matmul-graph family (dense/moe); "
            f"{args.arch} is {cfg.family!r}"
        )
    if args.fabric_mesh and args.fabric_chips > 1:
        ap.error("pass either --fabric-mesh or the --fabric-chips sugar, not both")
    rollup = None
    if args.fabric:
        # map (and optionally shard) BEFORE serving so the batching log line
        # carries the per-request fabric cost, not just a post-hoc printout;
        # one mapped pass covers the whole lock-step batch (tokens = batch),
        # which is what lets the mesh's data axis actually split work
        import jax as _jax

        from repro.fabric import (
            ChipMeshConfig,
            FabricConfig,
            execute_sharded_matmul,
            fabric_report,
            map_matmul,
            map_model,
            resolve_backend,
            shard_model,
            shard_placement,
            sharded_fabric_report,
        )

        fb = FabricConfig(mode=args.fabric, n_arrays=args.fabric_arrays)
        if args.fabric_mesh:
            try:
                mesh_d, mesh_m = parse_fabric_mesh(args.fabric_mesh)
            except ValueError as e:
                ap.error(str(e))
        else:
            side = {1: 1, 4: 2, 16: 4}[args.fabric_chips]
            mesh_d = mesh_m = side
        if mesh_d * mesh_m > 1:
            cm = ChipMeshConfig(data=mesh_d, model=mesh_m, fabric=fb)
            sps = shard_model(cfg, cm, tokens=st.batch)
            rollup = sharded_fabric_report(sps, cm)
        else:
            cm = ChipMeshConfig(fabric=fb)
            sps = []
            rollup = fabric_report(map_model(cfg, fb, tokens=st.batch), fb)

        # resolve the backend against the REAL model placements: one layer
        # with a replication fallback is enough to keep the whole pass
        # sequential (and an explicit shard_map request fails loudly on it)
        smoke_m, smoke_k, smoke_n = 2 * cm.data, cm.model * fb.rows, fb.cols
        sp = shard_placement(map_matmul("smoke", smoke_m, smoke_k, smoke_n, fb), cm)
        resolved = {resolve_backend(p, args.fabric_backend) for p in sps or [sp]}
        backend = "sequential" if "sequential" in resolved else "shard_map"
        # numeric backend validation: run one mesh-divisible matmul through
        # the resolved backend so the log line reports a path that executed
        skey = _jax.random.PRNGKey(0)
        x_s = _jax.random.normal(skey, (smoke_m, smoke_k))
        w_s = _jax.random.normal(_jax.random.fold_in(skey, 1), (smoke_k, smoke_n))
        from repro.core.cim_linear import CiMConfig as _CiM

        execute_sharded_matmul(
            x_s, w_s, cm,
            _CiM(mode="bitplane", a_bits=4, w_bits=4, adc_bits=fb.adc_bits,
                 rows=fb.rows, ste=False),
            sharded=sp, backend=backend,
        )
        rollup["exec_backend"] = backend
        print(
            f"[serve] fabric exec backend: {backend} "
            f"({len(_jax.devices())} jax device(s) for {cm.n_chips} chip(s))"
        )

        if args.fabric_program:
            # fused forward as the validation pass: the full-transformer-
            # block GRAPH (siblings, attention mixing, norms, residuals —
            # repro.fabric.graph) for families with a matmul-graph forward,
            # the residual-CHAIN program (repro.fabric.program) for the
            # rest (mamba/hybrid). Either way the fused path falls back to
            # its reference loop (with printed reasons) when the served
            # model's shapes are not eligible on this mesh.
            import numpy as _np

            from repro.fabric import measure_forward

            val_cim = _CiM(
                mode="bitplane", a_bits=4, w_bits=4, adc_bits=fb.adc_bits,
                rows=fb.rows, ste=False,
            )
            if cfg.family in ("dense", "moe"):
                from repro.fabric import compile_graph_forward
                from repro.fabric.report import graph_section

                # --fabric-scan validates the FULL model (the scan is what
                # makes its compile depth-constant); otherwise one block
                prog = compile_graph_forward(
                    cfg, cm, cim=val_cim, backend=args.fabric_backend,
                    tokens=st.batch, block_only=not args.fabric_scan,
                    scan_layers=args.fabric_scan,
                )
                xp = _jax.random.normal(
                    _jax.random.PRNGKey(2), (st.batch, 1, prog.d_in)
                )
                rollup["graph"] = graph_section(prog.graph, cm.model, program=prog)
                if args.fabric_scan:
                    desc = (f"graph: scanned {prog.n_blocks}-block model "
                            f"({len(prog.placements)} matmuls, block traced once)")
                else:
                    desc = (f"graph: {len(prog.graph.nodes)}-node block "
                            f"({len(prog.placements)} matmuls)")
                ref_name = "per-node loop"
            else:
                from repro.fabric import compile_forward

                prog = compile_forward(
                    cfg, cm, cim=val_cim, backend=args.fabric_backend,
                    tokens=st.batch, block_only=True,
                )
                xp = prog.example_input(_jax.random.PRNGKey(2))
                desc = f"chain: {prog.n_layers}-layer block"
                ref_name = "per-layer loop"
            wsp = prog.random_weights(_jax.random.PRNGKey(3))
            y_f = prog(xp, wsp)
            y_l = prog.reference_forward(xp, wsp, backend="sequential")
            maxdiff = float(_np.abs(_np.asarray(y_f) - _np.asarray(y_l)).max())
            # reference baseline on the sequential loop: the auto-fallback
            # path, and cheap enough to keep serving startup interactive
            measured = measure_forward(
                prog, x=xp, weights=wsp, iters=1,
                per_layer_backend="sequential", per_layer_iters=1,
            )
            measured["max_abs_diff_vs_per_layer"] = maxdiff
            rollup["program_validation"] = measured
            mc = measured.get("measured_collective_s")
            print(
                f"[serve] fused {desc} on {prog.backend}"
                + (f" (fallback: {'; '.join(prog.problems)})" if prog.problems else "")
                + f", maxdiff {maxdiff:.2e} vs {ref_name}; collectives "
                + (f"{mc*1e3:.3g} ms wall" if mc is not None else "n/a")
                + f" vs modeled link {measured['modeled_link_s']*1e3:.3g} ms"
            )

        if args.fabric_autotune:
            # cost-model-driven continuous batching: pick mesh + bucket
            # boundaries for a synthetic ragged request mix (every batch
            # size up to --batch, uniform — a stand-in for a measured
            # trace), then validate one ragged batch through the bucketed
            # fused-program cache against the per-node reference
            import numpy as _np

            from repro.fabric import (
                BucketedGraphCache,
                autotune_plan,
                autotune_section,
                request_histogram,
            )

            at_cim = _CiM(
                mode="bitplane", a_bits=4, w_bits=4, adc_bits=fb.adc_bits,
                rows=fb.rows, ste=False,
            )
            hist = request_histogram(range(1, st.batch + 1))
            plan = autotune_plan(
                cfg, hist, cm.n_chips, fb, cim=at_cim,
                default_mesh=(mesh_d, mesh_m),
            )
            plan_cm = ChipMeshConfig(data=plan.data, model=plan.model, fabric=fb)
            cache = BucketedGraphCache(
                cfg, plan_cm, at_cim, buckets=plan.buckets,
                block_only=not args.fabric_scan, scan_layers=args.fabric_scan,
            )
            # a batch the plan's data axis does NOT divide, when one exists
            b_val = next(
                (b for b in range(st.batch, 0, -1) if b % plan.data),
                st.batch,
            )
            prog = cache.program_for(cache.bucket_for(b_val))
            w_at = prog.random_weights(_jax.random.PRNGKey(3))
            x_at = _jax.random.normal(_jax.random.PRNGKey(2), (b_val, 1, prog.d_in))
            y_bucketed = cache(x_at, w_at)
            y_ref = prog.reference_forward(x_at, w_at)
            at_diff = float(_np.abs(_np.asarray(y_bucketed) - _np.asarray(y_ref)).max())
            rollup["autotune"] = autotune_section(plan, cache)
            print(
                f"[serve] autotune: mesh {plan.data}x{plan.model}, buckets "
                f"{list(plan.buckets)} ({plan.searched} plans searched); "
                f"expected {plan.expected_latency_s*1e3:.3g} ms/request vs "
                f"baseline {plan.baseline_latency_s*1e3:.3g} ms; ragged "
                f"B={b_val} via bucketed fused path, maxdiff {at_diff:.2e} "
                f"vs per-node reference"
            )

    out = serve_batch(cfg, st, fabric_rollup=rollup)
    print(
        f"[serve] {args.arch}: prefill {out['prefill_s']*1e3:.1f} ms, "
        f"decode {out['decode_tok_s']:.1f} tok/s "
        f"(batch {st.batch}, +{st.gen_len} tokens)"
    )
    print("[serve] sample generation:", out["generated"][0][:16].tolist())

    if rollup is not None:
        from repro.fabric import render_markdown

        print()
        print(render_markdown(rollup))


if __name__ == "__main__":
    main()
