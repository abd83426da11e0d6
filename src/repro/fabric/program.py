"""Whole-model fused forward: compile a mapped chain into ONE shard_map program.

``fabric.shard.execute_sharded_matmul`` runs one matmul at a time: every layer
gathers its combined output to the host, re-scatters it as the next layer's
input, and pays a Python dispatch. The paper's area argument is system-level —
memory-immersed digitization buys more resident arrays per chip, which only
pays off if the *whole network* runs on the fabric with minimal external
traffic — so this module compiles the entire forward pass into a single
jitted SPMD program:

  * layer i's ``psum_scatter`` output **stays sharded** as layer i+1's input —
    the reduce-scatter leaves chip ``c`` holding exactly the output columns
    that are its K-slice of the next layer (tile-aligned by construction), so
    no gather/re-scatter happens between layers and ONE ``all_gather`` at the
    very end produces the full output;
  * inter-layer re-quantization stays sharded too: the global activation
    abs-max is a scalar ``pmax`` over the mesh (max of shard maxes IS the
    global max, exactly), so the fused program quantizes bit-identically to
    the per-layer loop's host-side ``quantize_symmetric``;
  * per-layer ADC noise keys are ``fold_in(key, layer_index)``-derived, then
    per-chip / per-tile like every other executor (``fabric.tiles``), so a
    1x1 mesh is bit-for-bit the per-layer ``execute_sharded_matmul`` loop —
    noisy ADC included — and a multi-chip mesh matches it to float tolerance.

:func:`measure_forward` closes the validation loop the ROADMAP asks for: it
wall-clocks the fused collectives (block-until-ready, fused program minus an
identical program with the collectives stripped) and reports the measured
time next to ``overlapped_mesh_latency``'s modeled link time
(``fabric.pipeline.link_validation``). The two live in different clock
domains — host-simulation seconds vs modeled 10 MHz-fabric seconds — so the
ratio is a calibration constant tracked across PRs (``tools/ci_check.py`` ->
``BENCH_fabric_program.json``), not a number expected to be 1.
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.cim_linear import CimStats, CiMConfig, quantize_symmetric
from repro.fabric.mapper import model_forward_chain
from repro.fabric.shard import (
    ShardedPlacement,
    _chip_noise_key,
    _record_conversions,
    execute_sharded_matmul,
    shard_model,
)
from repro.fabric.tiles import column_tile_matmul
from repro.fabric.topology import ChipMeshConfig
from repro.launch.mesh import make_chip_mesh
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.fallback import (
    REASON_RAGGED_BATCH,
    REASON_REQUESTED_SEQUENTIAL,
    classify_fallback,
    record_fallback,
)

__all__ = [
    "FabricProgram",
    "compile_forward",
    "per_layer_forward",
    "measure_forward",
    "program_eligibility",
]


def _record_request(component: str, program, m: int, fused: bool) -> None:
    """Host-side per-request accounting shared by the chain and graph
    programs: one ``fabric_requests_total{path=...}`` increment, plus — on
    the fused path only, whose collectives never pass through
    ``execute_sharded_matmul`` — the analytic conversion/link-bit totals the
    per-layer loop would otherwise record matmul by matmul. Reads nothing
    traced; no-op when metrics collection is inactive."""
    if not obs_metrics.active():
        return
    obs_metrics.inc(
        "fabric_requests_total",
        help="Forward requests by execution path (fused shard_map vs fallback loop).",
        path="fused" if fused else "fallback",
    )
    if fused:
        cim = program.cim
        rows = program.chip_mesh.fabric.rows
        obs_metrics.inc(
            "fabric_matmuls_total",
            len(program.placements),
            help="Mapped matmuls executed.",
        )
        _record_conversions(
            cim,
            sum(
                cim.a_bits * cim.w_bits * m * math.ceil(sp.k / rows) * sp.n
                for sp in program.placements
            ),
        )
        obs_metrics.inc(
            "fabric_link_bits_total",
            # crosschip_bits_per_pass is priced at the placement's planned M;
            # scale to the rows actually served — exact, since the bits are
            # (k_splits-1) * M * N * psum_bits, linear in M
            sum(
                sp.crosschip_bits_per_pass * m // sp.m
                for sp in program.placements
            ),
            help="Cross-chip reduce-scatter bits moved per executed matmul.",
        )


def _record_request_fallback(component: str, program, detail: str = "") -> None:
    """Classify and emit the structured fallback record for a request that
    left the fused path (``__call__``'s sequential branches)."""
    if program.problems:
        reason = classify_fallback(program.problems)
        detail = detail or "; ".join(program.problems)
    elif program.requested_backend == "sequential":
        reason = REASON_REQUESTED_SEQUENTIAL
    else:
        reason = REASON_RAGGED_BATCH
    record_fallback(component, reason, detail)

_COLLECTIVE_PRIMS = ("all_gather", "reduce_scatter", "psum", "pmax", "ppermute", "all_to_all")


def _count_collectives(fn, args) -> dict:
    """Count collective primitives in ``fn``'s jaxpr (recursing into nested
    jaxprs) — shared by the chain program's and the graph program's
    collective census. A ``lax.scan`` body executes once per iteration, so
    the walk multiplies everything inside it by the scan's trip count: a
    scan-over-layers program therefore reports its per-block census x
    ``n_layers``, directly comparable to the unrolled program's budget."""
    jaxpr = jax.make_jaxpr(fn)(*args)
    counts = {name: 0 for name in _COLLECTIVE_PRIMS}

    def walk(j, mult=1):
        for eqn in j.eqns:
            if eqn.primitive.name in counts:
                counts[eqn.primitive.name] += mult
            inner_mult = mult
            if eqn.primitive.name == "scan":
                inner_mult = mult * eqn.params.get("length", 1)
            for v in eqn.params.values():
                for item in v if isinstance(v, (list, tuple)) else [v]:
                    inner = getattr(item, "jaxpr", item)
                    if hasattr(inner, "eqns"):
                        walk(inner, inner_mult)

    walk(jaxpr.jaxpr)
    return counts


def shard_forward_chain(
    cfg: ModelConfig,
    chip_mesh: ChipMeshConfig,
    tokens: int = 1,
    cim: Optional[CiMConfig] = None,
    block_only: bool = False,
) -> List[ShardedPlacement]:
    """Shard the model's forward chain (``mapper.model_forward_chain``) onto
    the mesh — ``shard_model``'s own offset-bookkeeping walk, restricted to
    the chained linears the fused program can run end to end."""
    return shard_model(
        cfg, chip_mesh, tokens=tokens, cim=cim,
        matmuls=model_forward_chain(cfg, tokens, block_only=block_only),
    )


def program_eligibility(
    placements: Sequence[ShardedPlacement], chip_mesh: ChipMeshConfig
) -> List[str]:
    """Why the fused shard_map program can('t) run this chain. Empty = eligible.

    Beyond ``resolve_backend``'s per-layer conditions (devices, no
    replication fallbacks), the fusion needs the *chain* invariants: layer
    i's N is layer i+1's K; every K tile-aligns with the mesh
    (``K % (model * rows) == 0``, so the reduce-scatter hands each chip a
    whole-tile K-slice) and every N splits evenly for the tiled
    ``psum_scatter`` (``N % model == 0``).

    Example::

        >>> from repro.fabric import ChipMeshConfig, FabricConfig, map_matmul, shard_placement
        >>> fb = FabricConfig(mode="pair_sar", n_arrays=8)
        >>> cm = ChipMeshConfig(model=2, fabric=fb)
        >>> sps = [shard_placement(map_matmul("l", 4, 64, 64, fb), cm)]
        >>> program_eligibility(sps, cm)
        []
    """
    problems: List[str] = []
    if not placements:
        return ["empty layer chain"]
    fabric = chip_mesh.fabric
    n_dev = len(jax.devices())
    if n_dev < chip_mesh.n_chips:
        problems.append(
            f"host has {n_dev} jax device(s) < {chip_mesh.n_chips} chips (set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={chip_mesh.n_chips})"
        )
    prev = None
    for i, sp in enumerate(placements):
        if sp.chip_mesh != chip_mesh:
            problems.append(f"layer {i} ({sp.name}) was planned on a different mesh")
            continue
        if (sp.d_splits, sp.k_splits) != (chip_mesh.data, chip_mesh.model):
            problems.append(
                f"layer {i} ({sp.name}) has replication fallbacks: realized "
                f"{sp.d_splits}x{sp.k_splits} != mesh {chip_mesh.data}x{chip_mesh.model}"
            )
        if sp.k % (chip_mesh.model * fabric.rows) != 0:
            problems.append(
                f"layer {i} ({sp.name}) K={sp.k} is not a whole number of "
                f"{fabric.rows}-row tiles per model-axis chip"
            )
        if sp.n % chip_mesh.model != 0:
            problems.append(
                f"layer {i} ({sp.name}) N={sp.n} does not divide the model axis "
                f"({chip_mesh.model}) for the tiled psum_scatter"
            )
        if prev is not None:
            if sp.k != prev.n:
                problems.append(
                    f"chain break at layer {i}: {prev.name} outputs N={prev.n} "
                    f"but {sp.name} consumes K={sp.k}"
                )
            if sp.m != prev.m:
                problems.append(
                    f"batch mismatch at layer {i}: {prev.name} M={prev.m} vs "
                    f"{sp.name} M={sp.m}"
                )
        prev = sp
    return problems


@dataclasses.dataclass
class FabricProgram:
    """A compiled whole-model forward over the chip mesh.

    ``backend`` is the *resolved* execution path: ``"shard_map"`` runs the
    single fused SPMD program; ``"sequential"`` is the per-layer
    ``execute_sharded_matmul`` host loop (the automatic fallback, and the
    reference the fused path is tested bit-exact against on a 1x1 mesh).
    Call it like a function::

        y = program(x, weights, key=key)
        y, stats = program(x, weights, return_stats=True)

    ``weights`` is one float ``(K_i, N_i)`` matrix per chained layer
    (:attr:`weight_shapes`); quantization — per-tensor activations,
    per-column weights — matches the per-layer loop exactly.

    Example::

        >>> import jax
        >>> from repro.fabric import ChipMeshConfig, FabricConfig, compile_forward
        >>> from repro.core.cim_linear import CiMConfig
        >>> fb = FabricConfig(mode="pair_sar", n_arrays=8)
        >>> cim = CiMConfig(mode="bitplane", a_bits=4, w_bits=4, adc_bits=5, rows=16, ste=False)
        >>> prog = compile_forward(get_chain(), ChipMeshConfig(fabric=fb), cim)  # doctest: +SKIP
        >>> y = prog(x, prog.random_weights(jax.random.PRNGKey(0)))  # doctest: +SKIP
    """

    chip_mesh: ChipMeshConfig
    cim: CiMConfig
    placements: List[ShardedPlacement]
    backend: str  # resolved: "shard_map" | "sequential"
    requested_backend: str
    problems: List[str]  # why shard_map was ineligible (empty when it runs)
    _fns: dict = dataclasses.field(default_factory=dict, repr=False)

    @property
    def n_layers(self) -> int:
        return len(self.placements)

    @property
    def weight_shapes(self) -> List[Tuple[int, int]]:
        return [(sp.k, sp.n) for sp in self.placements]

    @property
    def m(self) -> int:
        return self.placements[0].m

    def random_weights(self, key: jax.Array) -> List[jnp.ndarray]:
        """Per-layer standard-normal weights of the chain's shapes
        (``fold_in(key, i)`` per layer) — for smokes, examples, tests."""
        return [
            jax.random.normal(jax.random.fold_in(key, i), (k, n))
            for i, (k, n) in enumerate(self.weight_shapes)
        ]

    def example_input(self, key: jax.Array) -> jnp.ndarray:
        """An ``(M, K0)`` input matching the planned chain shapes."""
        return jax.random.normal(key, (self.m, self.placements[0].k))

    def reference_forward(self, x, weights, key=None, backend: str = "sequential",
                          return_stats: bool = False):
        """The per-layer ``execute_sharded_matmul`` loop on this program's
        placements — what ``measure_forward`` times as the unfused baseline."""
        return per_layer_forward(
            x, weights, self.placements, self.chip_mesh, self.cim,
            key=key, backend=backend, return_stats=return_stats,
        )

    # -- fused SPMD program -------------------------------------------------

    def _fused(self, has_key: bool, collectives: bool = True):
        """Build (and cache) the jitted shard_map program.

        ``collectives=False`` compiles an identical program with every
        collective replaced by a local stand-in of the same shape —
        numerically wrong by construction, but the same per-chip compute, so
        ``t(fused) - t(local)`` isolates the collectives' wall time for
        :func:`measure_forward`.
        """
        cache_key = (has_key, collectives)
        if cache_key in self._fns:
            return self._fns[cache_key]
        cm, cim = self.chip_mesh, self.cim
        fabric = cm.fabric
        C, D = cm.model, cm.data
        cols = fabric.cols
        L = self.n_layers
        mesh = make_chip_mesh(D, C, require_concrete=True)
        qmax = (1 << (cim.a_bits - 1)) - 1 if cim.a_signed else (1 << cim.a_bits) - 1
        lo = -qmax - 1 if cim.a_signed else 0

        # qmax enters as a TRACED operand, not a literal: XLA strength-reduces
        # division by a constant into multiplication by its rounded reciprocal,
        # which would put the fused activation scale one ulp off the per-layer
        # loop's host-side quantize_symmetric and break 1x1 bit-exactness
        def chip_fn(x_blk, qmax_f, *flat):
            ws = flat[: 2 * L]
            key = flat[2 * L] if has_key else None
            di = jax.lax.axis_index("data")
            ci = jax.lax.axis_index("model")
            conversions = jnp.zeros((), jnp.int32)
            comparisons = jnp.zeros((), jnp.int32)
            h = x_blk
            for i in range(L):
                w_blk, sw_blk = ws[2 * i], ws[2 * i + 1]
                # global activation scale: max of shard maxes == global max,
                # exactly — bit-identical to the loop's quantize_symmetric
                absval = jnp.abs(h) if cim.a_signed else jnp.maximum(h, 0)
                absmax = jnp.max(absval)
                if collectives:
                    absmax = jax.lax.pmax(absmax, ("data", "model"))
                scale = jnp.where(absmax > 0, absmax / qmax_f, 1.0)
                x_int = jnp.clip(jnp.round(h / scale), lo, qmax)
                lkey = jax.random.fold_in(key, i) if has_key else None
                # K-shard index only: data chips differ via the global row
                # ids (row_offset), keeping each row's draws split-invariant
                chip_key = _chip_noise_key(lkey, ci) if has_key else None
                y_int, st = column_tile_matmul(
                    x_int, w_blk, cim, cols, key=chip_key,
                    row_offset=di * x_int.shape[0],
                )
                conversions = conversions + st.conversions
                comparisons = comparisons + st.comparisons
                if C > 1:
                    if collectives:
                        # the inter-layer combine: chip ci keeps exactly its
                        # K-slice of the NEXT layer — no gather, no re-scatter
                        y_int = jax.lax.psum_scatter(
                            y_int, "model", scatter_dimension=1, tiled=True
                        )
                    else:
                        nc = y_int.shape[1] // C
                        y_int = jax.lax.dynamic_slice_in_dim(y_int, ci * nc, nc, axis=1)
                h = y_int * scale * sw_blk
            if C > 1:
                if collectives:
                    h = jax.lax.all_gather(h, "model", axis=1, tiled=True)  # the ONE gather
                else:
                    h = jnp.concatenate([h] * C, axis=1)
            if collectives:
                conversions = jax.lax.psum(conversions, ("data", "model"))
                comparisons = jax.lax.psum(comparisons, ("data", "model"))
            return h, conversions, comparisons

        in_specs = [P("data", "model"), P()]
        for _ in range(L):
            in_specs += [P("model", None), P(None, "model")]
        if has_key:
            in_specs.append(P())
        fn = jax.jit(
            jax.shard_map(
                chip_fn,
                mesh=mesh,
                in_specs=tuple(in_specs),
                out_specs=(P("data", None), P(), P()),
                check_vma=False,
            )
        )
        self._fns[cache_key] = fn
        return fn

    def _prepare(self, x, weights, key):
        """Flatten x, quantize weights host-side (exactly the per-layer
        loop's front-end), and assemble the fused program's argument list."""
        if len(weights) != self.n_layers:
            raise ValueError(f"expected {self.n_layers} weight matrices, got {len(weights)}")
        for i, (w, (k, n)) in enumerate(zip(weights, self.weight_shapes)):
            if tuple(w.shape) != (k, n):
                raise ValueError(
                    f"layer {i} ({self.placements[i].name}) expects weights "
                    f"({k}, {n}), got {tuple(w.shape)}"
                )
        batch_shape = x.shape[:-1]
        k0 = self.placements[0].k
        if x.shape[-1] != k0:
            raise ValueError(f"input features {x.shape[-1]} != chain K={k0}")
        xm = x.reshape(-1, k0)
        qmax = (
            (1 << (self.cim.a_bits - 1)) - 1 if self.cim.a_signed
            else (1 << self.cim.a_bits) - 1
        )
        flat = [jnp.float32(qmax)]
        for w in weights:
            w_int, sw = quantize_symmetric(w, self.cim.w_bits, self.cim.w_signed, per_axis=-1)
            flat += [w_int, sw]
        if key is not None:
            flat.append(key)
        return batch_shape, xm, flat

    def _fused_args(self, x, weights, key):
        """The fused callable's concrete argument tuple (measure_forward)."""
        _, xm, flat = self._prepare(x, weights, key)
        return (xm, *flat)

    def fused_available(self, x) -> bool:
        """Whether the fused shard_map path can run THIS input — the
        resolved backend plus ``__call__``'s ragged-batch condition
        (flattened rows divisible by the data axis), exposed so
        ``measure_forward`` never traces an infeasible shape."""
        if self.backend != "shard_map":
            return False
        return x.reshape(-1, x.shape[-1]).shape[0] % self.chip_mesh.data == 0

    def __call__(self, x, weights, key: Optional[jax.Array] = None, return_stats: bool = False):
        if self.backend != "shard_map":
            _record_request_fallback("fabric.program", self)
            _record_request("fabric.program", self, 0, fused=False)
            return per_layer_forward(
                x, weights, self.placements, self.chip_mesh, self.cim,
                key=key, backend="sequential", return_stats=return_stats,
            )
        batch_shape, xm, flat = self._prepare(x, weights, key)
        if xm.shape[0] % self.chip_mesh.data:
            if self.requested_backend == "shard_map":
                raise ValueError(
                    f"fused program unavailable: batch rows {xm.shape[0]} are "
                    f"not divisible by the data axis ({self.chip_mesh.data})"
                )
            record_fallback(
                "fabric.program", REASON_RAGGED_BATCH,
                f"batch rows {xm.shape[0]} % data axis {self.chip_mesh.data} != 0",
            )
            _record_request("fabric.program", self, 0, fused=False)
            return per_layer_forward(
                x, weights, self.placements, self.chip_mesh, self.cim,
                key=key, backend="sequential", return_stats=return_stats,
            )
        _record_request("fabric.program", self, xm.shape[0], fused=True)
        with obs_trace.span(
            "fabric.program.forward", n_layers=self.n_layers,
            mesh=f"{self.chip_mesh.data}x{self.chip_mesh.model}", m=xm.shape[0],
        ):
            y, conversions, comparisons = self._fused(key is not None)(xm, *flat)
        y = y.reshape(*batch_shape, self.placements[-1].n)
        if return_stats:
            return y, CimStats(conversions, comparisons)
        return y

    # -- introspection ------------------------------------------------------

    def collective_counts(self, x=None, weights=None, key=None) -> dict:
        """Count collective primitives in the fused program's jaxpr —
        the acceptance check that the whole forward contains at most ONE
        ``all_gather`` (and one tiled ``reduce_scatter`` per inter-layer
        combine) lives on this."""
        if self.backend != "shard_map":
            raise ValueError("collective_counts needs the shard_map backend")
        if x is None:
            x = jnp.zeros((self.m, self.placements[0].k))
        if weights is None:
            weights = [jnp.zeros(s) for s in self.weight_shapes]
        _, xm, flat = self._prepare(x, weights, key)
        return _count_collectives(self._fused(key is not None), (xm, *flat))


def compile_forward(
    model: Union[ModelConfig, Sequence[ShardedPlacement]],
    chip_mesh: ChipMeshConfig,
    cim: Optional[CiMConfig] = None,
    backend: str = "auto",
    tokens: int = 1,
    block_only: bool = False,
) -> FabricProgram:
    """Compile a whole mapped model into one fused shard_map forward.

    ``model`` is a :class:`~repro.configs.base.ModelConfig` (its forward
    chain — ``mapper.model_forward_chain`` — is sharded onto the mesh with
    the usual round-robin offsets) or an explicit list of chained
    :class:`~repro.fabric.shard.ShardedPlacement`\\ s. ``backend`` mirrors
    ``resolve_backend``: ``"shard_map"`` raises with the reasons when the
    fused program is ineligible (:func:`program_eligibility`), ``"auto"``
    falls back to the per-layer sequential loop — but unlike the per-matmul
    dispatcher, ``auto`` fuses even on a 1x1 mesh (killing per-layer Python
    dispatch is the point, one chip or many).

    Example::

        >>> import jax
        >>> from repro.core.cim_linear import CiMConfig
        >>> from repro.fabric import ChipMeshConfig, FabricConfig, compile_forward
        >>> fb = FabricConfig(mode="pair_sar", n_arrays=8)
        >>> cim = CiMConfig(mode="bitplane", a_bits=4, w_bits=4, adc_bits=5, rows=16, ste=False)
        >>> from repro.fabric import map_matmul, shard_placement
        >>> cm = ChipMeshConfig(fabric=fb)
        >>> chain = [shard_placement(map_matmul("l0", 4, 64, 64, fb, cim=cim), cm),
        ...          shard_placement(map_matmul("l1", 4, 64, 32, fb, cim=cim), cm)]
        >>> prog = compile_forward(chain, cm, cim)
        >>> x = jax.random.normal(jax.random.PRNGKey(0), (4, 64))
        >>> prog(x, prog.random_weights(jax.random.PRNGKey(1))).shape
        (4, 32)
    """
    if backend not in ("auto", "sequential", "shard_map"):
        raise ValueError(f"unknown backend {backend!r}")
    if cim is None:
        cim = CiMConfig(
            mode="bitplane", adc_bits=chip_mesh.fabric.adc_bits,
            rows=chip_mesh.fabric.rows, ste=False,
        )
    if cim.mode not in ("bitplane", "fake_quant"):
        raise ValueError(f"fabric execution needs bitplane|fake_quant, got {cim.mode!r}")
    if cim.ste:
        raise ValueError(
            "the fused forward feeds layer outputs straight into the next "
            "layer's quantizer; STE wrapping is a per-matmul training "
            "feature — pass a cim with ste=False"
        )
    if isinstance(model, ModelConfig):
        placements = shard_forward_chain(
            model, chip_mesh, tokens=tokens, cim=cim, block_only=block_only
        )
    else:
        placements = list(model)
    problems = program_eligibility(placements, chip_mesh)
    if backend == "sequential":
        resolved = "sequential"
    elif problems:
        if backend == "shard_map":
            raise ValueError("fused shard_map program unavailable: " + "; ".join(problems))
        obs_trace.event("fabric.program.ineligible", problems=list(problems))
        resolved = "sequential"
    else:
        resolved = "shard_map"
    return FabricProgram(
        chip_mesh=chip_mesh,
        cim=cim,
        placements=placements,
        backend=resolved,
        requested_backend=backend,
        problems=problems,
    )


def per_layer_forward(
    x,
    weights,
    placements: Sequence[ShardedPlacement],
    chip_mesh: ChipMeshConfig,
    cim: CiMConfig,
    key: Optional[jax.Array] = None,
    backend: str = "sequential",
    return_stats: bool = False,
):
    """The reference forward: one ``execute_sharded_matmul`` per layer, with
    the program's per-layer noise keys (``fold_in(key, i)``) — the loop the
    fused program is bit-exact against on a 1x1 mesh. Also the measured
    baseline for the per-layer gather + re-scatter + dispatch cost the
    fusion removes.

    Example::

        >>> import jax
        >>> from repro.core.cim_linear import CiMConfig
        >>> from repro.fabric import ChipMeshConfig, FabricConfig, map_matmul, shard_placement
        >>> from repro.fabric.program import per_layer_forward
        >>> fb = FabricConfig(mode="pair_sar", n_arrays=8)
        >>> cim = CiMConfig(mode="bitplane", a_bits=4, w_bits=4, adc_bits=5, rows=16, ste=False)
        >>> cm = ChipMeshConfig(fabric=fb)
        >>> sps = [shard_placement(map_matmul("l0", 4, 64, 32, fb, cim=cim), cm)]
        >>> x = jax.random.normal(jax.random.PRNGKey(0), (4, 64))
        >>> w = jax.random.normal(jax.random.PRNGKey(1), (64, 32))
        >>> per_layer_forward(x, [w], sps, cm, cim).shape
        (4, 32)
    """
    if len(weights) != len(placements):
        raise ValueError(f"expected {len(placements)} weight matrices, got {len(weights)}")
    h = x
    conversions = jnp.zeros((), jnp.int32)
    comparisons = jnp.zeros((), jnp.int32)
    for i, (sp, w) in enumerate(zip(placements, weights)):
        lkey = jax.random.fold_in(key, i) if key is not None else None
        h, st = execute_sharded_matmul(
            h, w, chip_mesh, cim, sharded=sp, key=lkey,
            return_stats=True, backend=backend,
        )
        conversions = conversions + st.conversions
        comparisons = comparisons + st.comparisons
    if return_stats:
        return h, CimStats(conversions, comparisons)
    return h


def _time_best(fn, iters: int) -> float:
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def measure_forward(
    program,
    x=None,
    weights=None,
    key: Optional[jax.Array] = None,
    iters: int = 2,
    per_layer_backend: Optional[str] = None,
    per_layer_iters: int = 1,
    per_layer: bool = True,
) -> dict:
    """Wall-clock a fused program and isolate its collectives' time.

    ``program`` is a chain :class:`FabricProgram` or a full-block
    :class:`~repro.fabric.graph.GraphProgram` — both expose the fused /
    collective-stripped twins and a ``reference_forward`` unfused baseline.
    Runs (block-until-ready, best of ``iters`` after a warmup): the fused
    program; an identical program with the collectives replaced by local
    stand-ins of the same shapes (so the difference is the collectives'
    wall time); and the per-layer/per-node reference loop (the
    gather-per-layer baseline the fusion removes — ``per_layer_backend``
    defaults to the program's own backend, and its dispatch/trace overhead
    per call is real steady-state cost, so it is timed with
    ``per_layer_iters`` to keep smokes budgeted; ``per_layer=False`` skips
    the baseline entirely, how the CI calibration-stability re-measure
    stays cheap). The measured collective seconds land next to the modeled
    link time via ``fabric.pipeline.link_validation`` — measured
    host-simulation seconds vs modeled fabric seconds, the
    ``link_clock_calibration`` constant tracked across PRs.

    Example::

        >>> r = measure_forward(prog)  # doctest: +SKIP
        >>> sorted(r)[:3]  # doctest: +SKIP
        ['backend', 'fused_s', 'local_s']
    """
    from repro.fabric.pipeline import link_validation

    if x is None:
        x = program.example_input(jax.random.PRNGKey(0))
    if weights is None:
        weights = program.random_weights(jax.random.PRNGKey(1))

    out = {
        "backend": program.backend,
        "n_layers": program.n_layers,
        "mesh": f"{program.chip_mesh.data}x{program.chip_mesh.model}",
        "n_chips": program.chip_mesh.n_chips,
    }
    measured_collective_s = None
    # fused_available also screens ragged batches (__call__'s documented
    # fallback), which the fused twins cannot trace
    if program.backend == "shard_map" and program.fused_available(x):
        args = program._fused_args(x, weights, key)
        fused = program._fused(key is not None)
        local = program._fused(key is not None, collectives=False)
        jax.block_until_ready(fused(*args))  # compile + warm
        jax.block_until_ready(local(*args))
        out["fused_s"] = _time_best(lambda: fused(*args), iters)
        out["local_s"] = _time_best(lambda: local(*args), iters)
        measured_collective_s = max(0.0, out["fused_s"] - out["local_s"])
    if per_layer:
        loop_backend = per_layer_backend or program.backend
        out["per_layer_backend"] = loop_backend
        reference = lambda: program.reference_forward(  # noqa: E731 — timed thunk
            x, weights, key=key, backend=loop_backend
        )
        jax.block_until_ready(reference())  # warm the reference caches too
        out["per_layer_s"] = _time_best(reference, per_layer_iters)
        if "fused_s" in out:
            out["fused_speedup_vs_per_layer"] = out["per_layer_s"] / max(out["fused_s"], 1e-12)
    out.update(link_validation(program.placements, measured_collective_s))
    return out
