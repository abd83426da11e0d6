"""Full-transformer-block fused forward: compile a ForwardGraph into ONE
shard_map program.

``fabric.program.compile_forward`` fuses only the residual *chain*
(q -> o -> gate -> down -> unembed): the k/v/up/router siblings and every
mixing op between the linears are dropped, so the fused program is a
cost-model artifact rather than the model the paper's collaborative CiM
fabric would actually serve. This module executes the COMPLETE block stack
(``mapper.model_forward_graph``) — siblings, attention mixing, SiLU gating,
norms, residual adds — in one jitted SPMD program over the chip mesh:

  * the residual stream stays feature-sharded over the ``model`` axis the
    whole way: every scatter-combined matmul ends in a tiled
    ``psum_scatter`` whose output slice is exactly the consumer's
    tile-aligned K-slice, and ONE trailing ``all_gather`` produces the
    logits;
  * sibling branches (k/v/up) consume the SAME quantized layer input as
    their chained partner — one re-quantization boundary (a scalar ``pmax``)
    per *distinct* matmul input, not per matmul — and pay one extra
    reduce-scatter each, enumerated (never silently added) by
    ``ForwardGraph.collective_budget`` and asserted against
    ``GraphProgram.collective_counts``;
  * attention mixing runs chip-local: with ``n_heads % model == 0`` and
    ``n_kv_heads % model == 0`` the k/v scatters hand every chip whole
    GQA head groups, so ``softmax(q kᵀ) v`` (RoPE-free causal, as in
    ``models/transformer``) needs NO collective, and the chip's mixed heads
    are precisely its K-slice of ``o_proj``;
  * norms are the only ops that read across the sharded feature axis: the
    sum of squares is a per-row ``psum`` over ``model``; the MoE router —
    whose softmax needs the whole expert axis — recombines via ``psum``
    instead of a scatter and gates the ONE activated expert (``expert0``).

Numerics mirror ``fabric.program`` exactly: activation quantization uses a
TRACED ``qmax`` operand (XLA would otherwise strength-reduce the scale
division and drift one ulp), per-node ADC noise keys are
``fold_in(key, matmul_index)`` then per-chip/per-tile like every other
executor, and every matmul runs the shared ``fabric.tiles`` inner loop — so
on a 1x1 mesh the fused graph is bit-for-bit :func:`per_node_forward` (the
per-node ``execute_sharded_matmul`` + shared-mixing-helper reference loop),
noisy ADC included, and matches it on real multi-chip meshes.

:func:`transformer_graph_weights` closes the real-weights loop: it adapts
``models.transformer.init_transformer`` parameters into the graph's weight
dict, so actual model logits — not synthetic chains — run on the fabric.

``compile_graph_forward(scan_layers=True)`` is the depth-constant form:
the repeated block (``mapper.model_block_template``) traces ONCE and runs
under ``jax.lax.scan`` over weights stacked on a leading layer axis
(:func:`stack_block_weights` / :func:`unstack_block_weights`), the
embed-side norm and unembed stay outside the scan, the residual stream
stays feature-sharded across iterations, and per-layer noise keys are
derived inside the body from the traced global matmul index — so the
scanned program is still bit-for-bit the unrolled one on a 1x1 mesh while
trace+compile cost stops growing with ``n_layers``.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.cim_linear import CimStats, CiMConfig, quantize_symmetric
from repro.fabric.mapper import ForwardGraph, model_block_template, model_forward_graph
from repro.fabric.shard import (
    ShardedPlacement,
    _chip_noise_key,
    execute_sharded_matmul,
    shard_model,
)
from repro.fabric.tiles import column_tile_matmul
from repro.fabric.topology import ChipMeshConfig
from repro.launch.mesh import make_chip_mesh
from repro.obs import scopes
from repro.obs import trace as obs_trace
from repro.obs.fallback import REASON_RAGGED_BATCH, record_fallback
from repro.fabric.program import _record_request, _record_request_fallback

__all__ = [
    "GraphProgram",
    "compile_graph_forward",
    "per_node_forward",
    "graph_eligibility",
    "shard_forward_graph",
    "transformer_graph_weights",
    "stack_block_weights",
    "unstack_block_weights",
]

_NEG = -1e30


# ---------------------------------------------------------------------------
# Shared non-CiM ops — ONE definition used by the fused program and the
# per-node reference, which is what makes their bit-exactness structural
# ---------------------------------------------------------------------------


def _attention_mix(q, k, v, n_heads: int, n_kv_heads: int, head_dim: int):
    """RoPE-free causal GQA mixing ``softmax(q kᵀ / sqrt(hd)) v``.

    ``q``: (B, S, n_heads*hd); ``k``/``v``: (B, S, n_kv_heads*hd). Heads are
    independent, so the fused program calls this per chip on its head slice
    and the reference on all heads — identical per-head arithmetic.
    """
    b, s, _ = q.shape
    g = n_heads // n_kv_heads
    qh = q.reshape(b, s, n_kv_heads, g, head_dim)
    kh = k.reshape(b, s, n_kv_heads, head_dim)
    vh = v.reshape(b, s, n_kv_heads, head_dim)
    scores = jnp.einsum(
        "bqkgd,bckd->bqkgc", qh, kh, preferred_element_type=jnp.float32
    ) * (1.0 / np.sqrt(head_dim))
    pos = jnp.arange(s)
    mask = pos[None, :] <= pos[:, None]  # key c visible to query q iff c <= q
    scores = jnp.where(mask[None, :, None, None, :], scores, _NEG)
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores - m) * mask[None, :, None, None, :].astype(jnp.float32)
    out = jnp.einsum("bqkgc,bckd->bqkgd", p, vh, preferred_element_type=jnp.float32)
    out = out / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    return out.reshape(b, s, n_heads * head_dim)


def _norm_apply(h, scale, eps: float, d_total, sumsq):
    """RMS norm given the (possibly psum-combined) sum of squares over the
    FULL feature axis; matches ``models.layers.rms_norm``'s
    ``x * rsqrt(mean(x^2) + eps) * (1 + scale)`` form.

    ``d_total`` must be a RUNTIME f32 scalar, not a Python literal: inside
    the fused jit a literal divisor gets strength-reduced to a rounded
    reciprocal (the same one-ulp drift the traced ``qmax`` guards against in
    ``fabric.program``), while the eager reference performs a true division.
    """
    inv = jax.lax.rsqrt(sumsq / d_total + eps)
    return h * inv * (1.0 + scale)


def _silu_gate(gate, up):
    return jax.nn.silu(gate) * up


def _expert0_prob(router_logits):
    """Softmax probability of the one activated expert (expert0) — the
    graph's documented MoE semantics: a token's critical path runs through
    ONE expert; the other top_k - 1 run in parallel, not in series."""
    return jax.nn.softmax(router_logits, axis=-1)[..., :1]


# ---------------------------------------------------------------------------
# Planning
# ---------------------------------------------------------------------------


def shard_forward_graph(
    cfg: ModelConfig,
    chip_mesh: ChipMeshConfig,
    tokens: int = 1,
    cim: Optional[CiMConfig] = None,
    block_only: bool = False,
) -> Tuple[ForwardGraph, List[ShardedPlacement]]:
    """Build the model's forward graph and shard every matmul node onto the
    mesh — ``shard_model``'s own offset-bookkeeping walk over the graph's
    matmul list, so graph costs and chain costs come from one planner.

    Example::

        >>> from repro.configs.registry import get_config
        >>> from repro.fabric import ChipMeshConfig, FabricConfig, shard_forward_graph
        >>> cm = ChipMeshConfig(fabric=FabricConfig(mode="hybrid", n_arrays=60))
        >>> g, sps = shard_forward_graph(get_config("smollm-135m"), cm, tokens=4,
        ...                              block_only=True)
        >>> len(sps) == len(g.matmul_nodes)
        True
    """
    graph = model_forward_graph(cfg, tokens, block_only=block_only)
    placements = shard_model(
        cfg, chip_mesh, tokens=tokens, cim=cim, matmuls=graph.matmuls()
    )
    return graph, placements


def graph_eligibility(
    graph: ForwardGraph,
    placements: Sequence[ShardedPlacement],
    chip_mesh: ChipMeshConfig,
) -> List[str]:
    """Why the fused graph program can('t) run. Empty = eligible.

    Beyond the per-matmul conditions of ``program_eligibility`` (devices,
    no replication fallbacks, ``K % (model * rows) == 0``, ``N % model``
    for scatter-combined nodes), the graph needs the mixing invariants:
    attention heads must divide the model axis (``n_heads % model == 0``
    and ``n_kv_heads % model == 0``) so the k/v scatters hand every chip
    whole GQA head groups and mixing stays chip-local.

    Example::

        >>> from repro.configs.registry import get_config
        >>> from repro.fabric import ChipMeshConfig, FabricConfig, shard_forward_graph
        >>> from repro.fabric.graph import graph_eligibility
        >>> cm = ChipMeshConfig(fabric=FabricConfig(mode="hybrid", n_arrays=60))
        >>> g, sps = shard_forward_graph(get_config("smollm-135m"), cm, tokens=4,
        ...                              block_only=True)
        >>> graph_eligibility(g, sps, cm)
        []
    """
    problems: List[str] = []
    mm_nodes = graph.matmul_nodes
    if not mm_nodes:
        return ["empty graph"]
    fabric = chip_mesh.fabric
    C = chip_mesh.model
    n_dev = len(jax.devices())
    if n_dev < chip_mesh.n_chips:
        problems.append(
            f"host has {n_dev} jax device(s) < {chip_mesh.n_chips} chips (set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={chip_mesh.n_chips})"
        )
    if len(placements) != len(mm_nodes):
        return problems + [
            f"graph has {len(mm_nodes)} matmul nodes but {len(placements)} "
            "placements were supplied"
        ]
    for node, sp in zip(mm_nodes, placements):
        if (sp.name, sp.k, sp.n) != (node.name, node.k, node.n):
            problems.append(
                f"placement {sp.name} (K={sp.k}, N={sp.n}) does not match "
                f"graph node {node.name} (K={node.k}, N={node.n})"
            )
            continue
        if sp.chip_mesh != chip_mesh:
            problems.append(f"{sp.name} was planned on a different mesh")
            continue
        if (sp.d_splits, sp.k_splits) != (chip_mesh.data, chip_mesh.model):
            problems.append(
                f"{sp.name} has replication fallbacks: realized "
                f"{sp.d_splits}x{sp.k_splits} != mesh {chip_mesh.data}x{chip_mesh.model}"
            )
        if sp.k % (C * fabric.rows) != 0:
            problems.append(
                f"{sp.name} K={sp.k} is not a whole number of "
                f"{fabric.rows}-row tiles per model-axis chip"
            )
        if node.combine == "scatter" and sp.n % C != 0:
            problems.append(
                f"{sp.name} N={sp.n} does not divide the model axis ({C}) "
                "for the tiled psum_scatter"
            )
    for node in graph.nodes:
        if node.op == "attention":
            if node.n_heads % C or node.n_kv_heads % C:
                problems.append(
                    f"{node.name}: heads {node.n_heads}/{node.n_kv_heads} (q/kv) "
                    f"do not divide the model axis ({C}); chip-local GQA mixing "
                    "needs whole head groups per chip"
                )
    return problems


# ---------------------------------------------------------------------------
# The fused program
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class GraphProgram:
    """A compiled full-block forward graph over the chip mesh.

    Call it like a function on ``(B, S, d_model)`` embeddings::

        y = program(x, weights, key=key)           # (B, S, N_out)
        y, stats = program(x, weights, return_stats=True)

    ``weights`` is a dict keyed by node name: one float ``(K, N)`` matrix
    per matmul node and one ``(d,)`` scale vector per norm node
    (:meth:`weight_shapes`; :func:`transformer_graph_weights` builds it from
    real ``init_transformer`` params, :meth:`random_weights` from a key).
    ``backend`` is the resolved path: ``"shard_map"`` runs the single fused
    SPMD program, ``"sequential"`` the per-node reference loop
    (:func:`per_node_forward`) — also the automatic fallback when the
    runtime batch does not divide the data axis (the documented ragged-batch
    path).

    Example::

        >>> import jax
        >>> from repro.fabric import ChipMeshConfig, FabricConfig, compile_graph_forward
        >>> prog = compile_graph_forward(cfg, ChipMeshConfig(fabric=fb), cim)  # doctest: +SKIP
        >>> y = prog(x, prog.random_weights(jax.random.PRNGKey(0)))  # doctest: +SKIP
    """

    graph: ForwardGraph
    chip_mesh: ChipMeshConfig
    cim: CiMConfig
    placements: List[ShardedPlacement]  # aligned with graph.matmul_nodes
    backend: str  # resolved: "shard_map" | "sequential"
    requested_backend: str
    problems: List[str]  # why shard_map was ineligible (empty when it runs)
    # scan-over-layers form (compile_graph_forward(scan_layers=True)): the
    # repeated block traces ONCE and runs under lax.scan over weights stacked
    # on a leading layer axis; block_graph/tail_graph are the
    # mapper.model_block_template pair and n_blocks the scan trip count.
    # graph/placements still describe the full unrolled model (budget,
    # reports, reference loop); only the traced program changes shape.
    scan_layers: bool = False
    block_graph: Optional[ForwardGraph] = None
    tail_graph: Optional[ForwardGraph] = None
    n_blocks: int = 0
    _fns: dict = dataclasses.field(default_factory=dict, repr=False)

    @property
    def n_layers(self) -> int:
        """Matmul-node count (the unit measure_forward reports)."""
        return len(self.placements)

    @property
    def m(self) -> int:
        return self.graph.m

    @property
    def d_in(self) -> int:
        return self.graph.d_in

    @property
    def n_out(self) -> int:
        out = self.graph.node(self.graph.output)
        return out.n if out.op == "matmul" else self.graph.d_in

    def weight_shapes(self) -> Dict[str, Tuple[int, ...]]:
        """Expected shape per weighted node: ``(K, N)`` for matmuls,
        ``(d,)`` for norm scales. The scanned form instead keys the repeated
        block's weights once under the ``block.`` prefix with a leading
        ``n_blocks`` layer axis (``stack_block_weights`` builds that dict
        from real ``init_transformer`` params)."""
        shapes: Dict[str, Tuple[int, ...]] = {}
        if self.scan_layers:
            L = self.n_blocks
            for nd in self.block_graph.weighted_nodes():
                shapes[nd.name] = (
                    (L, nd.k, nd.n) if nd.op == "matmul" else (L, nd.d)
                )
            for nd in self.tail_graph.weighted_nodes():
                shapes[nd.name] = (nd.k, nd.n) if nd.op == "matmul" else (nd.d,)
            return shapes
        for nd in self.graph.weighted_nodes():
            shapes[nd.name] = (nd.k, nd.n) if nd.op == "matmul" else (nd.d,)
        return shapes

    def random_weights(self, key: jax.Array) -> Dict[str, jnp.ndarray]:
        """Standard-normal matmul weights and 0.1-scaled norm scales
        (``fold_in(key, i)`` per weighted node) — for smokes and tests. The
        scanned form stacks the SAME per-layer draws on the leading layer
        axis, so one key yields corresponding weights in both forms."""
        out: Dict[str, jnp.ndarray] = {}
        for i, nd in enumerate(self.graph.weighted_nodes()):
            k = jax.random.fold_in(key, i)
            if nd.op == "matmul":
                out[nd.name] = jax.random.normal(k, (nd.k, nd.n))
            else:
                out[nd.name] = 0.1 * jax.random.normal(k, (nd.d,))
        if self.scan_layers:
            return _stack_layer_weights(out, self.n_blocks)
        return out

    def example_input(self, key: jax.Array) -> jnp.ndarray:
        """A ``(B, S, d)`` input matching the planned token count ``m`` —
        batch set to the data axis when it divides (the fused-eligible
        shape), else a single sequence."""
        b = self.chip_mesh.data if self.m % self.chip_mesh.data == 0 else 1
        return jax.random.normal(key, (b, self.m // b, self.d_in))

    # -- fused SPMD program -------------------------------------------------

    def _fused(self, has_key: bool, collectives: bool = True):
        """Build (and cache) the jitted shard_map graph program.

        ``collectives=False`` compiles the timing twin: every collective is
        replaced by a local stand-in of the same shape (numerically wrong by
        construction, same per-chip compute) so ``t(fused) - t(local)``
        isolates the collectives' wall time for ``measure_forward``.
        """
        cache_key = (has_key, collectives)
        if cache_key in self._fns:
            return self._fns[cache_key]
        cm, cim, graph = self.chip_mesh, self.cim, self.graph
        fabric = cm.fabric
        C, D = cm.model, cm.data
        cols = fabric.cols
        mesh = make_chip_mesh(D, C, require_concrete=True)
        qmax = (1 << (cim.a_bits - 1)) - 1 if cim.a_signed else (1 << cim.a_bits) - 1
        lo = -qmax - 1 if cim.a_signed else 0
        scan = self.scan_layers
        if scan:
            block, tail = self.block_graph, self.tail_graph
            block_weighted = block.weighted_nodes()
            tail_weighted = tail.weighted_nodes()
            mm_per_block = len(block.matmul_nodes)
            n_blocks = self.n_blocks
        else:
            weighted = graph.weighted_nodes()

        def parse_params(nodes_weighted, args):
            """flat args -> {name: (w_int, sw) | scale}; returns args used."""
            params, i = {}, 0
            for nd in nodes_weighted:
                if nd.op == "matmul":
                    params[nd.name] = (args[i], args[i + 1])  # (w_int, sw)
                    i += 2
                else:
                    params[nd.name] = args[i]
                    i += 1
            return params, i

        # qmax is a TRACED operand for the same reason as fabric.program: a
        # literal divisor gets strength-reduced to a rounded reciprocal,
        # putting the fused activation scale one ulp off the reference's
        # host-side quantize_symmetric. one_f is a traced 1.0 that guards
        # the graph's other eager-vs-jit seam: whole-program fusion lets
        # LLVM contract `residual + (y_int*scale*sw)` into a single-rounding
        # FMA (optimization_barrier is stripped before fusion on CPU).
        # Multiplying each add-feeding node output by the runtime one_f
        # leaves only `fma(y, 1, residual) == round(y + residual)` — the
        # eager reference's exact arithmetic. Both guards survive the scan
        # body unchanged: qmax_f/one_f stay traced operands closed over by
        # the body, so XLA cannot specialize on them per iteration either.
        # mask_blk is this data-shard's (b_loc, 1, 1) slice of the pad-row
        # mask: 1.0 on real rows, 0.0 on bucket padding. Multiplying it into
        # every matmul node output keeps pad rows at exactly zero through the
        # whole block stack — a noisy ADC turns a zero input row into nonzero
        # codes (the half-LSB mav bias sits inside comparator sigma), which
        # would otherwise leak into the GLOBAL absmax at the next
        # re-quantization boundary and perturb real rows' scales. For real
        # rows `y * 1.0` is bitwise identity, and a fused
        # `fma(y, 1.0, residual) == round(y + residual)` — the same guard
        # argument as one_f, so unpadded results are untouched.
        def chip_fn(x_blk, qmax_f, one_f, mask_blk, *flat):
            key = flat[-1] if has_key else None
            di = jax.lax.axis_index("data")
            ci = jax.lax.axis_index("model")
            b_loc, s = x_blk.shape[0], x_blk.shape[1]

            @jax.named_scope(scopes.FABRIC_REQUANT)
            def requant(h):
                """A matmul input's global absmax scale and integer codes."""
                absval = jnp.abs(h) if cim.a_signed else jnp.maximum(h, 0)
                absmax = jnp.max(absval)
                if collectives:
                    # max of shard maxes IS the global max, exactly
                    absmax = jax.lax.pmax(absmax, ("data", "model"))
                scale = jnp.where(absmax > 0, absmax / qmax_f, 1.0)
                x_int = jnp.clip(jnp.round(h / scale), lo, qmax)
                return x_int.reshape(-1, x_int.shape[-1]), scale

            def run_nodes(nodes, vals, params, mm_idx0, conversions, comparisons):
                """ONE interpreter for a node list — the unrolled program,
                the scanned block body, and the out-of-scan tail all execute
                through it, which is what keeps their semantics identical.
                ``mm_idx0`` offsets the per-node noise keys so the scanned
                body reproduces the unrolled program's global
                ``fold_in(key, matmul_index)`` derivation exactly (it is a
                traced ``layer * mm_per_block`` inside the scan)."""
                qcache = {}  # input-node name -> (x_int 2d, scale): one
                # re-quantization boundary per DISTINCT matmul input, so
                # sibling branches share their producer's quantization
                mm_idx = 0
                for node in nodes:
                    with jax.named_scope(scopes.fabric_op(node.op)):
                        if node.op == "matmul":
                            src = node.inputs[0]
                            if src not in qcache:
                                qcache[src] = requant(vals[src])
                            x_int2, scale = qcache[src]
                            w_blk, sw_blk = params[node.name]
                            nkey = (
                                jax.random.fold_in(key, mm_idx0 + mm_idx)
                                if has_key else None
                            )
                            # K-shard index only: data chips are distinguished by
                            # the global row ids (row_offset), so each row's noise
                            # draws are invariant to the batch size and data split
                            chip_key = _chip_noise_key(nkey, ci) if has_key else None
                            y_int, st = column_tile_matmul(
                                x_int2, w_blk, cim, cols, key=chip_key,
                                row_offset=di * x_int2.shape[0],
                            )
                            conversions = conversions + st.conversions
                            comparisons = comparisons + st.comparisons
                            if node.combine == "scatter":
                                if C > 1:
                                    if collectives:
                                        # the combine that leaves chip ci holding its
                                        # tile-aligned K-slice of the consumer
                                        y_int = jax.lax.psum_scatter(
                                            y_int, "model", scatter_dimension=1, tiled=True
                                        )
                                    else:
                                        nc = y_int.shape[1] // C
                                        y_int = jax.lax.dynamic_slice_in_dim(
                                            y_int, ci * nc, nc, axis=1
                                        )
                            else:  # psum: the router's full replicated output
                                if collectives:
                                    y_int = jax.lax.psum(y_int, "model")
                            y = y_int * scale * sw_blk * one_f  # one_f: no FMA across
                            # the CiM boundary; mask_blk re-zeroes pad rows the
                            # noisy ADC lifted off zero (see chip_fn comment)
                            vals[node.name] = y.reshape(b_loc, s, -1) * mask_blk
                            mm_idx += 1
                        elif node.op == "norm":
                            h = vals[node.inputs[0]]
                            sumsq = jnp.sum(h * h, axis=-1, keepdims=True)
                            if collectives:
                                sumsq = jax.lax.psum(sumsq, "model")
                            vals[node.name] = _norm_apply(
                                h, params[node.name], node.eps, node.d * one_f, sumsq
                            )
                        elif node.op == "attention":
                            q, k_, v_ = (vals[nm] for nm in node.inputs)
                            vals[node.name] = _attention_mix(
                                q, k_, v_, node.n_heads // C, node.n_kv_heads // C,
                                node.head_dim,
                            )
                        elif node.op == "silu_gate":
                            vals[node.name] = _silu_gate(*(vals[nm] for nm in node.inputs))
                        elif node.op == "residual":
                            a, b = (vals[nm] for nm in node.inputs)
                            vals[node.name] = a + b
                        elif node.op == "moe_gate":
                            expert, router = (vals[nm] for nm in node.inputs)
                            # one_f: the gated product feeds a residual add — see above
                            vals[node.name] = expert * _expert0_prob(router) * one_f
                return vals, conversions, comparisons

            conversions = jnp.zeros((), jnp.int32)
            comparisons = jnp.zeros((), jnp.int32)
            if scan:
                stacked, used = parse_params(block_weighted, flat)
                tail_params, _ = parse_params(tail_weighted, flat[used:])

                def body(carry, xs):
                    h, conv, comp = carry
                    li, params_l = xs  # scan slices the leading layer axis
                    vals, conv, comp = run_nodes(
                        block.nodes, {"x": h}, params_l,
                        li * mm_per_block, conv, comp,
                    )
                    # the carry stays the feature-sharded residual stream:
                    # the block body never gathers, so iteration i+1 reads
                    # exactly the K-slice layout iteration i produced
                    return (vals[block.output], conv, comp), None

                (h, conversions, comparisons), _ = jax.lax.scan(
                    body,
                    (x_blk, conversions, comparisons),
                    (jnp.arange(n_blocks, dtype=jnp.int32), stacked),
                )
                vals, conversions, comparisons = run_nodes(
                    tail.nodes, {"x": h}, tail_params,
                    n_blocks * mm_per_block, conversions, comparisons,
                )
                out = vals[tail.output]
            else:
                params, _ = parse_params(weighted, flat)
                vals, conversions, comparisons = run_nodes(
                    graph.nodes, {"x": x_blk}, params, 0, conversions, comparisons
                )
                out = vals[graph.output]
            if C > 1:
                if collectives:
                    out = jax.lax.all_gather(out, "model", axis=2, tiled=True)
                else:
                    out = jnp.concatenate([out] * C, axis=2)
            if collectives:
                conversions = jax.lax.psum(conversions, ("data", "model"))
                comparisons = jax.lax.psum(comparisons, ("data", "model"))
            return out, conversions, comparisons

        in_specs: List = [P("data", None, "model"), P(), P(), P("data", None, None)]
        if scan:
            # stacked block weights: leading layer axis unsharded, the rest
            # sharded exactly like the unrolled per-layer specs
            for nd in block_weighted:
                if nd.op == "matmul":
                    in_specs.append(P(None, "model", None))
                    in_specs.append(
                        P(None, None, "model") if nd.combine == "scatter"
                        else P(None, None, None)
                    )
                else:
                    in_specs.append(P(None, "model"))
            tail_spec_nodes = tail_weighted
        else:
            tail_spec_nodes = weighted
        for nd in tail_spec_nodes:
            if nd.op == "matmul":
                in_specs.append(P("model", None))
                in_specs.append(
                    P(None, "model") if nd.combine == "scatter" else P(None, None)
                )
            else:
                in_specs.append(P("model"))
        if has_key:
            in_specs.append(P())
        fn = jax.jit(
            jax.shard_map(
                chip_fn,
                mesh=mesh,
                in_specs=tuple(in_specs),
                out_specs=(P("data", None, None), P(), P()),
                check_vma=False,
            )
        )
        self._fns[cache_key] = fn
        return fn

    def _prepare(self, x, weights, key, real_rows=None):
        """Validate shapes, quantize matmul weights host-side (exactly the
        reference loop's front-end), and assemble the fused argument list.

        ``real_rows`` marks the first ``real_rows`` batch rows as real and the
        rest as bucket padding (``fabric.autotune``): the pad-row mask operand
        zeroes padded rows at every matmul node so they cannot perturb the
        global quantization scales real rows see."""
        shapes = self.weight_shapes()
        missing = sorted(set(shapes) - set(weights))
        if missing:
            raise ValueError(f"missing graph weights: {missing}")
        if x.ndim != 3:
            raise ValueError(
                f"graph forward wants (batch, seq, d) embeddings; got {x.shape}"
            )
        if x.shape[-1] != self.d_in:
            raise ValueError(f"input features {x.shape[-1]} != graph d={self.d_in}")
        for name, shape in shapes.items():
            if tuple(weights[name].shape) != shape:
                raise ValueError(
                    f"node {name} expects weights {shape}, got "
                    f"{tuple(weights[name].shape)}"
                )
        qmax = (
            (1 << (self.cim.a_bits - 1)) - 1 if self.cim.a_signed
            else (1 << self.cim.a_bits) - 1
        )
        if real_rows is None:
            mask = jnp.ones((x.shape[0], 1, 1), jnp.float32)
        else:
            if not 1 <= real_rows <= x.shape[0]:
                raise ValueError(
                    f"real_rows={real_rows} outside [1, batch={x.shape[0]}]"
                )
            mask = (
                (jnp.arange(x.shape[0]) < real_rows)
                .astype(jnp.float32)
                .reshape(-1, 1, 1)
            )
        flat = [jnp.float32(qmax), jnp.float32(1.0), mask]
        if self.scan_layers:
            for nd in self.block_graph.weighted_nodes():
                w = weights[nd.name]
                if nd.op == "matmul":
                    # per-layer host-side quantization in a Python loop, NOT
                    # a vmap: each w[i] goes through the EXACT same
                    # quantize_symmetric call the unrolled program makes, so
                    # the scan body's sliced (w_int, sw) are bit-identical
                    per = [
                        quantize_symmetric(
                            w[i], self.cim.w_bits, self.cim.w_signed, per_axis=-1
                        )
                        for i in range(self.n_blocks)
                    ]
                    flat += [
                        jnp.stack([p[0] for p in per]),
                        jnp.stack([p[1] for p in per]),
                    ]
                else:
                    flat.append(jnp.asarray(w, jnp.float32))
            spec_nodes = self.tail_graph.weighted_nodes()
        else:
            spec_nodes = self.graph.weighted_nodes()
        for nd in spec_nodes:
            if nd.op == "matmul":
                w_int, sw = quantize_symmetric(
                    weights[nd.name], self.cim.w_bits, self.cim.w_signed, per_axis=-1
                )
                flat += [w_int, sw]
            else:
                flat.append(jnp.asarray(weights[nd.name], jnp.float32))
        if key is not None:
            flat.append(key)
        return flat

    def _unrolled_weights(self, weights):
        """The per-layer weight dict the reference loop wants — stacked
        ``block.`` weights unstacked back to ``layer{i}.`` keys when this is
        a scanned program, passthrough otherwise."""
        if self.scan_layers:
            return unstack_block_weights(weights, self.n_blocks)
        return weights

    def _fused_args(self, x, weights, key, real_rows=None):
        """The fused callable's concrete argument tuple (measure_forward)."""
        return (x, *self._prepare(x, weights, key, real_rows=real_rows))

    def fused_available(self, x) -> bool:
        """Whether the fused shard_map path can run THIS input — the
        resolved backend plus ``__call__``'s ragged-batch condition (batch
        divisible by the data axis), exposed so ``measure_forward`` never
        traces an infeasible shape."""
        if self.backend != "shard_map" or x.ndim != 3:
            return False
        return x.shape[0] % self.chip_mesh.data == 0

    def __call__(self, x, weights, key: Optional[jax.Array] = None,
                 return_stats: bool = False, real_rows: Optional[int] = None):
        """Run the program. ``real_rows`` (``fabric.autotune``'s bucketed
        batches) declares that only the first ``real_rows`` batch rows are
        real and the rest are zero padding up to a bucket boundary: the fused
        program masks pad rows out of every matmul node, the returned logits
        are sliced back to ``real_rows``, and stats/metrics/EMA account only
        the real rows — so a padded run is bit-exact to, and reports exactly
        like, the unpadded reference."""
        b = x.shape[0]
        if real_rows is not None and not 1 <= real_rows <= b:
            raise ValueError(f"real_rows={real_rows} outside [1, batch={b}]")
        if self.backend != "shard_map" or b % self.chip_mesh.data:
            if self.backend == "shard_map":
                # fused program exists but THIS batch is ragged
                if self.requested_backend == "shard_map":
                    raise ValueError(
                        f"fused graph program unavailable: batch {b} is "
                        f"not divisible by the data axis ({self.chip_mesh.data})"
                    )
                # the documented ragged-batch path: fall back to the per-node
                # reference loop (bit-identical semantics, host dispatch)
                record_fallback(
                    "fabric.graph", REASON_RAGGED_BATCH,
                    f"batch {b} % data axis {self.chip_mesh.data} != 0",
                )
            else:
                _record_request_fallback("fabric.graph", self)
            _record_request("fabric.graph", self, 0, fused=False)
            # pad rows are pure bucket filler — the reference loop only ever
            # sees the real rows (per-row noise keys make that equivalent)
            x_ref = x if real_rows is None else x[:real_rows]
            return per_node_forward(
                x_ref, self._unrolled_weights(weights), self.graph,
                self.placements, self.chip_mesh, self.cim,
                key=key, backend="sequential", return_stats=return_stats,
            )
        with obs_trace.span("fabric.graph.prepare"):
            flat = self._prepare(x, weights, key, real_rows=real_rows)
        rows = b if real_rows is None else real_rows
        _record_request("fabric.graph", self, rows * x.shape[1], fused=True)
        with obs_trace.span(
            "fabric.graph.forward", n_matmuls=self.n_layers,
            mesh=f"{self.chip_mesh.data}x{self.chip_mesh.model}",
            tokens=rows * x.shape[1],
        ):
            y, conversions, comparisons = self._fused(key is not None)(x, *flat)
        if real_rows is not None:
            y = y[:real_rows]
            # conversions are per-row-constant (planes x k-tiles x columns
            # per row), so real_rows/b rescaling is exact; comparator counts
            # are data-dependent, so the pad-row share is removed
            # proportionally (pad rows digitize all-zero mavs)
            conversions = conversions * real_rows // b
            comparisons = comparisons * real_rows // b
        if return_stats:
            return y, CimStats(conversions, comparisons)
        return y

    def reference_forward(self, x, weights, key=None, backend: str = "sequential",
                          return_stats: bool = False):
        """The per-node reference loop on this program's placements — what
        ``measure_forward`` times as the unfused baseline. Accepts this
        program's own weight dict, stacked or not (scanned weights are
        unstacked back to ``layer{i}.`` keys first)."""
        return per_node_forward(
            x, self._unrolled_weights(weights), self.graph, self.placements,
            self.chip_mesh, self.cim,
            key=key, backend=backend, return_stats=return_stats,
        )

    # -- introspection ------------------------------------------------------

    def collective_counts(self, x=None, weights=None, key=None) -> dict:
        """Count collective primitives in the fused jaxpr — asserted equal
        to ``graph.collective_budget(model)``: per-sibling scatters are
        enumerated, ONE trailing all-gather, one pmax per re-quantization
        boundary, one psum per norm/router plus the two stats totals.

        The scanned form counts identically: the jaxpr walk multiplies
        collectives inside a ``scan`` body by its trip count, so one traced
        block reports per-block census × ``n_blocks`` — the same link
        traffic the unrolled program enumerates eqn by eqn. Tracing is
        ``jax.make_jaxpr`` only (no XLA compile), so this is cheap at any
        depth."""
        from repro.fabric.program import _count_collectives

        if self.backend != "shard_map":
            raise ValueError("collective_counts needs the shard_map backend")
        if x is None:
            b = self.chip_mesh.data
            x = jnp.zeros((b, max(1, self.m // b), self.d_in))
        if weights is None:
            weights = {
                name: jnp.zeros(shape) for name, shape in self.weight_shapes().items()
            }
        flat = self._prepare(x, weights, key)
        return _count_collectives(self._fused(key is not None), (x, *flat))

    def collective_budget(self) -> dict:
        """The documented budget (``ForwardGraph.collective_budget``) for
        this program's mesh."""
        return self.graph.collective_budget(self.chip_mesh.model)


def compile_graph_forward(
    model: Union[ModelConfig, ForwardGraph],
    chip_mesh: ChipMeshConfig,
    cim: Optional[CiMConfig] = None,
    backend: str = "auto",
    tokens: int = 1,
    block_only: bool = False,
    placements: Optional[Sequence[ShardedPlacement]] = None,
    scan_layers: bool = False,
) -> GraphProgram:
    """Compile a complete transformer-block stack into one fused shard_map
    forward over the chip mesh.

    ``model`` is a :class:`~repro.configs.base.ModelConfig` (its forward
    graph — ``mapper.model_forward_graph`` — is built and sharded with the
    usual round-robin offsets) or an explicit :class:`ForwardGraph` (with
    optional pre-sharded ``placements``). ``backend`` mirrors
    ``compile_forward``: ``"shard_map"`` raises with the reasons when the
    fused program is ineligible (:func:`graph_eligibility`), ``"auto"``
    falls back to the per-node loop — and fuses even on a 1x1 mesh, where
    killing the per-node Python dispatch is the point.

    ``scan_layers=True`` compiles the repeated transformer block ONCE and
    runs it under ``jax.lax.scan`` over weights stacked on a leading layer
    axis (``stack_block_weights`` builds that dict from real params;
    :meth:`GraphProgram.random_weights` stacks its own draws). Trace and
    compile cost become depth-constant while the logits stay bit-for-bit
    equal to the unrolled program on a 1x1 mesh, noisy ADC included — the
    per-layer noise keys are ``fold_in``-derived from a traced global
    matmul index inside the body, and the traced-qmax/traced-1.0 guards
    are closed over by the scan body unchanged. Requires a ``ModelConfig``
    (the block template comes from ``mapper.model_block_template``) and
    the full model (``block_only=False``).

    Example::

        >>> import jax
        >>> from repro.core.cim_linear import CiMConfig
        >>> from repro.fabric import ChipMeshConfig, FabricConfig, compile_graph_forward
        >>> from repro.configs.base import ModelConfig
        >>> cfg = ModelConfig(name="toy", family="dense", n_layers=1, d_model=64,
        ...                   vocab=64, n_heads=4, n_kv_heads=2, head_dim=16,
        ...                   d_ff=128, pad_vocab_multiple=16)
        >>> fb = FabricConfig(mode="pair_sar", n_arrays=8)
        >>> cim = CiMConfig(mode="bitplane", a_bits=4, w_bits=4, adc_bits=5, rows=16, ste=False)
        >>> prog = compile_graph_forward(cfg, ChipMeshConfig(fabric=fb), cim, tokens=4)
        >>> x = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 64))
        >>> prog(x, prog.random_weights(jax.random.PRNGKey(1))).shape
        (1, 4, 64)
    """
    if backend not in ("auto", "sequential", "shard_map"):
        raise ValueError(f"unknown backend {backend!r}")
    if scan_layers:
        if not isinstance(model, ModelConfig):
            raise ValueError(
                "scan_layers needs a ModelConfig: the repeated-block template "
                "comes from mapper.model_block_template, not an ad-hoc graph"
            )
        if block_only:
            raise ValueError(
                "scan_layers compiles the FULL model (the scan runs the "
                "block n_layers times); drop block_only"
            )
    if cim is None:
        cim = CiMConfig(
            mode="bitplane", adc_bits=chip_mesh.fabric.adc_bits,
            rows=chip_mesh.fabric.rows, ste=False,
        )
    if cim.mode not in ("bitplane", "fake_quant"):
        raise ValueError(f"fabric execution needs bitplane|fake_quant, got {cim.mode!r}")
    if cim.ste:
        raise ValueError(
            "the fused graph feeds node outputs straight into the next "
            "CiM boundary's quantizer; pass a cim with ste=False"
        )
    if isinstance(model, ModelConfig):
        graph, placements = shard_forward_graph(
            model, chip_mesh, tokens=tokens, cim=cim, block_only=block_only
        )
    else:
        graph = model
        if placements is None:
            placements = shard_model(
                None, chip_mesh, tokens=graph.m, cim=cim, matmuls=graph.matmuls()
            )
        else:
            placements = list(placements)
    problems = graph_eligibility(graph, placements, chip_mesh)
    if backend == "sequential":
        resolved = "sequential"
    elif problems:
        if backend == "shard_map":
            raise ValueError("fused graph program unavailable: " + "; ".join(problems))
        obs_trace.event("fabric.graph.ineligible", problems=list(problems))
        resolved = "sequential"
    else:
        resolved = "shard_map"
    block_graph = tail_graph = None
    n_blocks = 0
    if scan_layers:
        block_graph, tail_graph = model_block_template(model, tokens)
        n_blocks = model.n_layers
    return GraphProgram(
        graph=graph,
        chip_mesh=chip_mesh,
        cim=cim,
        placements=list(placements),
        backend=resolved,
        requested_backend=backend,
        problems=problems,
        scan_layers=scan_layers,
        block_graph=block_graph,
        tail_graph=tail_graph,
        n_blocks=n_blocks,
    )


def per_node_forward(
    x,
    weights: Dict[str, jnp.ndarray],
    graph: ForwardGraph,
    placements: Sequence[ShardedPlacement],
    chip_mesh: ChipMeshConfig,
    cim: CiMConfig,
    key: Optional[jax.Array] = None,
    backend: str = "sequential",
    return_stats: bool = False,
    key_fn=None,
):
    """The reference forward: one ``execute_sharded_matmul`` per matmul node
    plus the SAME shared mixing helpers as the fused program, with the
    program's per-node noise keys (``fold_in(key, matmul_index)``) — the
    loop the fused graph is bit-exact against on a 1x1 mesh, and the
    documented fallback for ragged batches.

    ``key_fn(key, matmul_index) -> node_key`` overrides the default
    derivation — the noise-key-independence tests use it to prove the
    scanned program would diverge if layers shared keys.

    Example::

        >>> import jax
        >>> from repro.core.cim_linear import CiMConfig
        >>> from repro.fabric import ChipMeshConfig, FabricConfig, compile_graph_forward
        >>> from repro.fabric.graph import per_node_forward
        >>> from repro.configs.base import ModelConfig
        >>> cfg = ModelConfig(name="toy", family="dense", n_layers=1, d_model=64,
        ...                   vocab=64, n_heads=4, n_kv_heads=2, head_dim=16,
        ...                   d_ff=128, pad_vocab_multiple=16)
        >>> fb = FabricConfig(mode="pair_sar", n_arrays=8)
        >>> cim = CiMConfig(mode="bitplane", a_bits=4, w_bits=4, adc_bits=5, rows=16, ste=False)
        >>> prog = compile_graph_forward(cfg, ChipMeshConfig(fabric=fb), cim, tokens=4)
        >>> x = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 64))
        >>> ws = prog.random_weights(jax.random.PRNGKey(1))
        >>> per_node_forward(x, ws, prog.graph, prog.placements,
        ...                  prog.chip_mesh, cim).shape
        (1, 4, 64)
    """
    if x.ndim != 3:
        raise ValueError(f"graph forward wants (batch, seq, d) embeddings; got {x.shape}")
    sp_by_name = {sp.name: sp for sp in placements}
    b, s = x.shape[0], x.shape[1]
    conversions = jnp.zeros((), jnp.int32)
    comparisons = jnp.zeros((), jnp.int32)
    vals = {"x": x}
    mm_idx = 0
    for node in graph.nodes:
        if node.op == "matmul":
            h = vals[node.inputs[0]]
            if key is None:
                nkey = None
            elif key_fn is not None:
                nkey = key_fn(key, mm_idx)
            else:
                nkey = jax.random.fold_in(key, mm_idx)
            y2, st = execute_sharded_matmul(
                h.reshape(-1, h.shape[-1]), weights[node.name], chip_mesh, cim,
                sharded=sp_by_name[node.name], key=nkey, return_stats=True,
                backend=backend,
            )
            conversions = conversions + st.conversions
            comparisons = comparisons + st.comparisons
            vals[node.name] = y2.reshape(b, s, -1)
            mm_idx += 1
        elif node.op == "norm":
            h = vals[node.inputs[0]]
            sumsq = jnp.sum(h * h, axis=-1, keepdims=True)
            vals[node.name] = _norm_apply(
                h, jnp.asarray(weights[node.name], jnp.float32), node.eps,
                jnp.float32(node.d), sumsq,
            )
        elif node.op == "attention":
            q, k_, v_ = (vals[nm] for nm in node.inputs)
            vals[node.name] = _attention_mix(
                q, k_, v_, node.n_heads, node.n_kv_heads, node.head_dim
            )
        elif node.op == "silu_gate":
            vals[node.name] = _silu_gate(*(vals[nm] for nm in node.inputs))
        elif node.op == "residual":
            a, b_ = (vals[nm] for nm in node.inputs)
            vals[node.name] = a + b_
        elif node.op == "moe_gate":
            expert, router = (vals[nm] for nm in node.inputs)
            vals[node.name] = expert * _expert0_prob(router)
        else:  # pragma: no cover
            raise ValueError(f"unknown graph op {node.op!r}")
    out = vals[graph.output]
    if return_stats:
        return out, CimStats(conversions, comparisons)
    return out


def transformer_graph_weights(
    params: dict, cfg: ModelConfig, block_only: bool = False
) -> Dict[str, jnp.ndarray]:
    """Adapt real ``models.transformer.init_transformer`` parameters into a
    graph weight dict — the end-to-end real-weights path.

    Matmul weights are cast to float32 (the fabric quantizes them itself,
    per column); norm scales map ``ln1``/``ln2``/``ln_f`` directly. MoE maps
    the router plus the ONE activated expert's (expert0) SwiGLU weights, per
    the graph's documented MoE semantics. ``block_only`` uses layer 0 under
    the ``block`` prefix. QKV biases are not representable on the fabric
    (the mapper places pure matmuls) and raise.

    Example::

        >>> import jax
        >>> from repro.configs.base import ModelConfig
        >>> from repro.models.transformer import init_transformer
        >>> from repro.fabric.graph import transformer_graph_weights
        >>> cfg = ModelConfig(name="toy", family="dense", n_layers=2, d_model=64,
        ...                   vocab=64, n_heads=4, n_kv_heads=2, head_dim=16,
        ...                   d_ff=128, pad_vocab_multiple=16, param_dtype="float32")
        >>> ws = transformer_graph_weights(init_transformer(jax.random.PRNGKey(0), cfg), cfg)
        >>> ws["layer0.q_proj"].shape, ws["ln_f"].shape, ws["unembed"].shape
        ((64, 64), (64,), (64, 64))
    """
    if cfg.qkv_bias:
        raise ValueError("the fabric graph maps pure matmuls; qkv_bias is unsupported")
    if cfg.family not in ("dense", "moe"):
        raise ValueError(f"no transformer graph for family {cfg.family!r}")
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    out: Dict[str, jnp.ndarray] = {}
    attn = params["attn"]
    for i in range(1 if block_only else cfg.n_layers):
        p = "block" if block_only else f"layer{i}"
        out[f"{p}.ln1"] = f32(params["ln1"][i])
        out[f"{p}.q_proj"] = f32(attn["wq"][i])
        out[f"{p}.k_proj"] = f32(attn["wk"][i])
        out[f"{p}.v_proj"] = f32(attn["wv"][i])
        out[f"{p}.o_proj"] = f32(attn["wo"][i])
        out[f"{p}.ln2"] = f32(params["ln2"][i])
        if cfg.n_experts:
            moe = params["moe"]
            out[f"{p}.router"] = f32(moe["router"][i])
            out[f"{p}.expert0.gate_proj"] = f32(moe["w_gate"][i, 0])
            out[f"{p}.expert0.up_proj"] = f32(moe["w_up"][i, 0])
            out[f"{p}.expert0.down_proj"] = f32(moe["w_down"][i, 0])
        else:
            mlp = params["mlp"]
            out[f"{p}.gate_proj"] = f32(mlp["w_gate"][i])
            out[f"{p}.up_proj"] = f32(mlp["w_up"][i])
            out[f"{p}.down_proj"] = f32(mlp["w_down"][i])
    if not block_only:
        from repro.models.layers import unembed_weight

        out["ln_f"] = f32(params["ln_f"])
        out["unembed"] = f32(unembed_weight(params["embed"], cfg))
    return out


def stack_block_weights(params: dict, cfg: ModelConfig) -> Dict[str, jnp.ndarray]:
    """Adapt real ``init_transformer`` parameters into the SCANNED graph
    weight dict: the repeated block's weights keyed once under the
    ``block.`` prefix with their native leading ``(n_layers, ...)`` axis —
    ``init_transformer`` already stacks every per-layer parameter, so this
    is a relabelling, not a copy — plus the out-of-scan tail (``ln_f``,
    ``unembed``). Slicing layer ``i`` off any stacked entry reproduces
    ``transformer_graph_weights``'s ``layer{i}.*`` entry exactly.

    Same representability rules as :func:`transformer_graph_weights`:
    pure matmuls only (``qkv_bias`` raises), dense or 1-activated-expert
    MoE (``expert0``).

    Example::

        >>> import jax
        >>> from repro.configs.base import ModelConfig
        >>> from repro.models.transformer import init_transformer
        >>> from repro.fabric.graph import stack_block_weights
        >>> cfg = ModelConfig(name="toy", family="dense", n_layers=2, d_model=64,
        ...                   vocab=64, n_heads=4, n_kv_heads=2, head_dim=16,
        ...                   d_ff=128, pad_vocab_multiple=16, param_dtype="float32")
        >>> ws = stack_block_weights(init_transformer(jax.random.PRNGKey(0), cfg), cfg)
        >>> ws["block.q_proj"].shape, ws["block.ln1"].shape, ws["unembed"].shape
        ((2, 64, 64), (2, 64), (64, 64))
    """
    if cfg.qkv_bias:
        raise ValueError("the fabric graph maps pure matmuls; qkv_bias is unsupported")
    if cfg.family not in ("dense", "moe"):
        raise ValueError(f"no transformer graph for family {cfg.family!r}")
    from repro.models.layers import unembed_weight

    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    attn = params["attn"]
    out: Dict[str, jnp.ndarray] = {
        "block.ln1": f32(params["ln1"]),
        "block.q_proj": f32(attn["wq"]),
        "block.k_proj": f32(attn["wk"]),
        "block.v_proj": f32(attn["wv"]),
        "block.o_proj": f32(attn["wo"]),
        "block.ln2": f32(params["ln2"]),
    }
    if cfg.n_experts:
        moe = params["moe"]
        out["block.router"] = f32(moe["router"])
        out["block.expert0.gate_proj"] = f32(moe["w_gate"][:, 0])
        out["block.expert0.up_proj"] = f32(moe["w_up"][:, 0])
        out["block.expert0.down_proj"] = f32(moe["w_down"][:, 0])
    else:
        mlp = params["mlp"]
        out["block.gate_proj"] = f32(mlp["w_gate"])
        out["block.up_proj"] = f32(mlp["w_up"])
        out["block.down_proj"] = f32(mlp["w_down"])
    out["ln_f"] = f32(params["ln_f"])
    out["unembed"] = f32(unembed_weight(params["embed"], cfg))
    return out


def unstack_block_weights(
    weights: Dict[str, jnp.ndarray], n_layers: int
) -> Dict[str, jnp.ndarray]:
    """The inverse adapter: a scanned (``block.``-stacked) weight dict back
    to the unrolled ``layer{i}.*`` form — each layer is a zero-copy slice
    of the stacked array, so the per-node reference loop sees exactly the
    weights the scan body would slice at iteration ``i``.

    Example::

        >>> import jax.numpy as jnp
        >>> from repro.fabric.graph import unstack_block_weights
        >>> ws = unstack_block_weights(
        ...     {"block.ln1": jnp.zeros((2, 4)), "ln_f": jnp.ones(4)}, 2)
        >>> sorted(ws)
        ['layer0.ln1', 'layer1.ln1', 'ln_f']
    """
    out: Dict[str, jnp.ndarray] = {}
    for name, w in weights.items():
        if name.startswith("block."):
            suffix = name[len("block."):]
            for i in range(n_layers):
                out[f"layer{i}.{suffix}"] = w[i]
        else:
            out[name] = w
    return out


def _stack_layer_weights(
    weights: Dict[str, jnp.ndarray], n_layers: int
) -> Dict[str, jnp.ndarray]:
    """Stack an unrolled ``layer{i}.*`` weight dict onto the leading layer
    axis under the ``block.`` prefix (random_weights' scanned form)."""
    out: Dict[str, jnp.ndarray] = {}
    done = set()
    for name in weights:
        if name.startswith("layer") and "." in name:
            suffix = name.split(".", 1)[1]
            if suffix in done:
                continue
            done.add(suffix)
            out[f"block.{suffix}"] = jnp.stack(
                [weights[f"layer{i}.{suffix}"] for i in range(n_layers)]
            )
        else:
            out[name] = weights[name]
    return out
