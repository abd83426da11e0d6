"""Chip-level collaborative CiM fabric (paper Figs. 1-3, 5c, Table I).

The paper's headline claim is system-level: memory-immersed digitization
shrinks the per-array ADC ~25x (vs SAR) / ~51x (vs Flash), so many more CiM
arrays fit in the same chip footprint — recovering the halved per-array
throughput of collaborative digitization and cutting external memory
accesses because more weights stay resident. This package models that chip:

  * :mod:`repro.fabric.topology` — ``FabricConfig``: a grid of CiM arrays
    wired as one of the paper's networking configurations (``pair_sar`` /
    ``flash`` / ``hybrid``) or a conventional dedicated-ADC baseline; array
    counts can be derived from an area budget via ``core.energy_area``.
  * :mod:`repro.fabric.mapper` — tile an arbitrary matmul (or a whole
    ``ModelConfig``) onto the fabric: K split across arrays at ``rows``
    boundaries, N across array columns, M across time; yields a placement
    plus weight-load (external-memory-access) counts.
  * :mod:`repro.fabric.pipeline` — cycle-pipelined multi-conversion schedule
    over N arrays (role swapping, shared flash-bank arbitration) extending
    ``core.schedule``; chip throughput / utilization and the iso-area
    throughput-recovery comparison.
  * :mod:`repro.fabric.tiles` — THE per-(column-tile, K-shard) inner loop
    (``column_tile_matmul`` + analytic ``fake_quant`` stats) every executor
    shares; one definition is what keeps the single-chip, sequential-loop,
    shard_map, and fused whole-model paths bit-for-bit interchangeable.
  * :mod:`repro.fabric.execute` — batched numerical execution of a mapped
    placement through the ``core.cim_linear`` machinery; a mapped layer
    matches the unmapped op bit-for-bit (noiseless ADC).
  * :mod:`repro.fabric.report` — per-layer and end-to-end
    area / energy / latency / EMA rollups, rendered like
    ``roofline.report``.
  * :mod:`repro.fabric.shard` — shard mapped placements across a mesh of
    chips (``ChipMeshConfig``): K-parallel tiles over the ``model`` axis
    (digital partial sums combined with a reduce-scatter over inter-chip
    links), batch over ``data``; divisibility fallbacks follow
    ``launch.shardings``. Execution backends: a host-sequential chip loop
    or a real multi-device ``jax.shard_map`` SPMD program
    (``backend="auto"|"sequential"|"shard_map"``, ``resolve_backend``).
    ``sharded_fabric_report`` separates on-chip EMA from cross-chip link
    traffic and reports double-buffered round-overlap latency
    (``overlapped_mesh_latency``).
  * :mod:`repro.fabric.program` — compile a whole mapped model into ONE
    fused shard_map forward (``compile_forward`` -> ``FabricProgram``):
    layer i's reduce-scatter output stays sharded as layer i+1's input,
    one all-gather at the end, per-layer ``fold_in`` noise keys; bit-exact
    vs the per-layer ``execute_sharded_matmul`` loop on a 1x1 mesh.
    ``measure_forward`` wall-clocks the fused collectives and
    ``pipeline.link_validation`` reports them next to the modeled link
    latency.
  * :mod:`repro.fabric.autotune` — continuous batching: a bucketed LRU of
    compiled graph programs (``BucketedGraphCache``) that zero-pads ragged
    batches onto the fused path bit-exactly, plus a mesh/bucket autotuner
    (``autotune_plan``) that searches the graph cost model for the cheapest
    feasible serving plan given a request-mix histogram.

Paper-figure correspondence: Fig. 1 (networking configurations) ->
``FabricConfig.mode``; Fig. 2 (pair SAR role swap) -> ``pair_sar`` groups;
Fig. 3 + 5c (hybrid shared flash bank) -> ``hybrid`` groups and the
pipeline's bank arbitration; Table I anchors the area/energy rollups.

See ``docs/fabric.md`` for the full architecture guide.
"""

from repro.fabric.autotune import (
    AutotunePlan,
    BucketedGraphCache,
    autotune_plan,
    autotune_section,
    request_histogram,
)
from repro.fabric.execute import execute_linear, execute_matmul
from repro.fabric.graph import (
    GraphProgram,
    compile_graph_forward,
    graph_eligibility,
    per_node_forward,
    shard_forward_graph,
    stack_block_weights,
    transformer_graph_weights,
    unstack_block_weights,
)
from repro.fabric.mapper import (
    ForwardGraph,
    GraphNode,
    LayerPlacement,
    TileAssignment,
    map_matmul,
    map_model,
    model_block_template,
    model_forward_chain,
    model_forward_graph,
    model_matmuls,
)
from repro.fabric.pipeline import (
    conversion_cycles,
    fabric_throughput,
    iso_area_comparison,
    link_validation,
    overlap_rounds,
    overlapped_mesh_latency,
    pipelined_schedule,
)
from repro.fabric.program import (
    FabricProgram,
    compile_forward,
    measure_forward,
    per_layer_forward,
    program_eligibility,
)
from repro.fabric.report import (
    fabric_report,
    graph_section,
    render_markdown,
    sharded_fabric_report,
)
from repro.fabric.shard import (
    ShardedPlacement,
    execute_sharded_matmul,
    resolve_backend,
    shard_model,
    shard_placement,
)
from repro.fabric.tiles import analytic_cim_stats, column_tile_matmul
from repro.fabric.topology import (
    BITCELL_UM2_65NM,
    MODES,
    ChipMeshConfig,
    FabricConfig,
    arrays_for_area,
)

__all__ = [
    "FabricConfig",
    "ChipMeshConfig",
    "MODES",
    "BITCELL_UM2_65NM",
    "arrays_for_area",
    "TileAssignment",
    "LayerPlacement",
    "map_matmul",
    "map_model",
    "model_matmuls",
    "model_forward_chain",
    "GraphNode",
    "ForwardGraph",
    "model_forward_graph",
    "model_block_template",
    "conversion_cycles",
    "fabric_throughput",
    "iso_area_comparison",
    "overlap_rounds",
    "overlapped_mesh_latency",
    "link_validation",
    "pipelined_schedule",
    "column_tile_matmul",
    "analytic_cim_stats",
    "execute_matmul",
    "execute_linear",
    "ShardedPlacement",
    "shard_placement",
    "shard_model",
    "resolve_backend",
    "execute_sharded_matmul",
    "FabricProgram",
    "compile_forward",
    "per_layer_forward",
    "measure_forward",
    "program_eligibility",
    "GraphProgram",
    "compile_graph_forward",
    "per_node_forward",
    "graph_eligibility",
    "shard_forward_graph",
    "transformer_graph_weights",
    "stack_block_weights",
    "unstack_block_weights",
    "fabric_report",
    "sharded_fabric_report",
    "graph_section",
    "render_markdown",
    "BucketedGraphCache",
    "AutotunePlan",
    "autotune_plan",
    "autotune_section",
    "request_histogram",
]
