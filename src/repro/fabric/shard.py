"""Shard mapped CiM fabrics across a mesh of chips (ROADMAP: multi-chip).

One chip (``FabricConfig``) holds a bounded number of resident weight tiles;
the paper's system argument — cheap memory-immersed digitization buys more
arrays, more resident weights, fewer external memory accesses — extends to a
*mesh* of such chips (:class:`repro.fabric.topology.ChipMeshConfig`):

  * ``model`` axis — a layer's K-parallel reduction tiles are split across
    chips at ``rows`` boundaries. Each chip digitizes the partial
    product-sums of its own K-slice locally (nothing analog ever crosses a
    chip boundary); the digital partials are combined with a ring
    **reduce-scatter** over the inter-chip links — the only new traffic the
    mesh introduces, priced separately from on-chip EMA in
    ``fabric.report``.
  * ``data`` axis — chips hold weight copies and split the batch (M); no
    cross-chip combine is needed.

Divisibility follows the production sharding rules: the split is planned with
``launch.shardings.spec_for`` (logical ``tp`` -> mesh ``model``, ``dp`` ->
``data``), and any dimension that does not divide its axis falls back to
replication *with the fallback recorded* — the same bookkeeping the dry-run
report uses, so an uneven layer silently costs nothing extra instead of
silently mis-mapping.

Numerics: :func:`execute_sharded_matmul` mirrors ``fabric.execute`` exactly —
fabric-level quantization once, then per (data-shard, column-tile, K-shard)
tile execution through ``core.cim_linear``'s per-plane machinery. On a 1x1
mesh it performs the identical operation sequence, so it is bit-for-bit equal
to the unsharded ``execute_matmul`` (asserted in ``tests/test_fabric_shard``).

Execution backends: ``backend="sequential"`` simulates every chip in a host
Python loop (runs anywhere); ``backend="shard_map"`` places the chips on a
real ``(data, model)`` jax device mesh (``launch.mesh.make_chip_mesh``) and
runs them as one SPMD program — each model-axis device computes its K-slice
partial sums locally and the digital combine is a ``jax.lax.psum_scatter``
reduce-scatter (+ gather) over the ``model`` axis, the collective whose link
traffic ``ShardedPlacement.crosschip_bits_per_pass`` prices. ``"auto"``
(default) picks ``shard_map`` whenever the host has enough devices and the
plan has no replication fallbacks, else falls back to the sequential loop.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import List, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.adc import takes_direct_path
from repro.core.cim_linear import (
    CimStats,
    CiMConfig,
    quantize_symmetric,
)
from repro.fabric.mapper import LayerPlacement, map_matmul, model_matmuls
from repro.fabric.tiles import column_tile_matmul
from repro.fabric.topology import ChipMeshConfig
from repro.launch import shardings as sh
from repro.launch.mesh import make_chip_mesh
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.fallback import REASON_RAGGED_BATCH, classify_fallback, record_fallback

__all__ = [
    "ShardedPlacement",
    "shard_placement",
    "shard_model",
    "resolve_backend",
    "execute_sharded_matmul",
]

BACKENDS = ("auto", "sequential", "shard_map")


@dataclasses.dataclass
class ShardedPlacement:
    """One layer's placement on a chip mesh, plus its cross-chip costs.

    ``chip`` is the per-chip :class:`~repro.fabric.mapper.LayerPlacement` of
    the K/M shard every chip actually executes (on a 1x1 mesh it is the whole
    layer). ``k_splits`` / ``d_splits`` are the *realized* split factors —
    equal to the mesh axes when the tile/batch counts divide, 1 (replication)
    when they don't, with each fallback recorded in ``fallbacks``.

    Example::

        >>> from repro.fabric import ChipMeshConfig, FabricConfig, shard_placement, map_matmul
        >>> cm = ChipMeshConfig(model=2, fabric=FabricConfig(mode="pair_sar", n_arrays=8))
        >>> sp = shard_placement(map_matmul("l", 4, 64, 64, cm.fabric), cm)
        >>> sp.k_splits, sp.chip.k_tiles, sp.crosschip_bits_per_pass > 0
        (2, 2, True)
    """

    name: str
    m: int
    k: int
    n: int
    chip_mesh: ChipMeshConfig
    chip: LayerPlacement  # what ONE chip runs (K/M shard mapped on its fabric)
    k_splits: int  # chips combining partial sums over the model axis
    d_splits: int  # batch shards over the data axis
    fallbacks: List[str]

    # -- cross-chip traffic (the mesh's only new cost) ----------------------

    @property
    def crosschip_bits_per_pass(self) -> int:
        """Total bits crossing chip links per forward pass: a ring
        reduce-scatter over ``k_splits`` chips moves ``(C-1)/C`` of each
        chip's (M_shard, N) partial-sum block, summed over chips and repeated
        per data-shard group — ``(C-1) * M * N * psum_bits`` in total."""
        if self.k_splits <= 1:
            return 0
        return (self.k_splits - 1) * self.m * self.n * self.chip_mesh.psum_bits

    @property
    def crosschip_energy_pj(self) -> float:
        return self.crosschip_bits_per_pass * self.chip_mesh.link_pj_per_bit

    @property
    def crosschip_latency_s(self) -> float:
        """Link time of the reduce-scatter: rings run in parallel across data
        groups, so the critical path is one chip's send volume."""
        if self.k_splits <= 1:
            return 0.0
        per_chip = (
            (self.k_splits - 1)
            / self.k_splits
            * (self.m // self.d_splits)
            * self.n
            * self.chip_mesh.psum_bits
        )
        return per_chip / self.chip_mesh.link_bits_per_s

    @property
    def n_chips_active(self) -> int:
        return self.k_splits * self.d_splits


def _k_slice(k: int, rows: int, k_tiles: int, k_splits: int, c: int) -> tuple:
    """Element range [k0, k1) of K-shard ``c`` (tile-granular, ragged tail)."""
    tiles_per = k_tiles // k_splits
    return c * tiles_per * rows, min(k, (c + 1) * tiles_per * rows)


def shard_placement(
    placement: LayerPlacement,
    chip_mesh: ChipMeshConfig,
    array_offset: int = 0,
) -> ShardedPlacement:
    """Partition one mapped layer across the chip mesh.

    K-parallel tiles go over the ``model`` axis, batch rows over ``data``,
    using the same ``spec_for`` divisibility rules (and scoped
    ``record_fallbacks`` bookkeeping) as the production param shardings: a
    K-tile count that does not divide the model axis — or a batch that does
    not divide the data axis — falls back to replication for that dimension.

    Example::

        >>> from repro.fabric import ChipMeshConfig, FabricConfig, map_matmul, shard_placement
        >>> fb = FabricConfig(mode="pair_sar", n_arrays=8)
        >>> sp = shard_placement(map_matmul("l", 4, 64, 64, fb), ChipMeshConfig(model=4, fabric=fb))
        >>> sp.k_splits, sp.chip.k
        (4, 16)
    """
    if placement.fabric != chip_mesh.fabric:
        raise ValueError("placement was mapped on a different FabricConfig than chip_mesh.fabric")
    mesh = chip_mesh.mesh()
    with sh.record_fallbacks() as fallbacks:
        spec = sh.spec_for(
            mesh,
            (placement.k_tiles, placement.m),
            ("tp", "dp"),
            label=f"fabric.shard/{placement.name}",
        )
    k_splits = sh.axes_size(mesh, ("model",)) if spec[0] is not None else 1
    d_splits = sh.axes_size(mesh, ("data",)) if spec[1] is not None else 1

    if k_splits == 1 and d_splits == 1 and array_offset == 0:
        chip = placement  # whole layer on every chip — exactly the 1-chip map
    else:
        k0, k1 = _k_slice(placement.k, placement.fabric.rows, placement.k_tiles, k_splits, 0)
        chip = map_matmul(
            placement.name,
            placement.m // d_splits,
            k1 - k0,
            placement.n,
            chip_mesh.fabric,
            cim=placement.cim,
            array_offset=array_offset,
        )
    return ShardedPlacement(
        name=placement.name,
        m=placement.m,
        k=placement.k,
        n=placement.n,
        chip_mesh=chip_mesh,
        chip=chip,
        k_splits=k_splits,
        d_splits=d_splits,
        fallbacks=fallbacks,
    )


def shard_model(
    cfg: ModelConfig,
    chip_mesh: ChipMeshConfig,
    tokens: int = 1,
    cim: Optional[CiMConfig] = None,
    block_only: bool = False,
    matmuls: Optional[List[tuple]] = None,
) -> List[ShardedPlacement]:
    """Map every linear of ``cfg`` onto the mesh (``map_model`` per chip-shard,
    round-robin array offsets preserved across layers).

    ``matmuls`` overrides the ``(name, M, K, N)`` list (default: all of
    ``model_matmuls``) — ``fabric.program`` passes the forward chain through
    here so both planners share ONE offset-bookkeeping walk.

    Example::

        >>> from repro.configs.registry import get_config
        >>> from repro.fabric import ChipMeshConfig, FabricConfig, shard_model
        >>> cm = ChipMeshConfig(model=4, fabric=FabricConfig(mode="hybrid", n_arrays=60))
        >>> sps = shard_model(get_config("smollm-135m"), cm, tokens=4, block_only=True)
        >>> len(sps), sps[0].k_splits
        (7, 4)
    """
    if matmuls is None:
        matmuls = model_matmuls(cfg, tokens, block_only=block_only)
    out: List[ShardedPlacement] = []
    offset = 0
    for name, m, k, n in matmuls:
        p = map_matmul(name, m, k, n, chip_mesh.fabric, cim=cim)
        sp = shard_placement(p, chip_mesh, array_offset=offset)
        offset = (offset + sp.chip.n_weight_tiles) % chip_mesh.fabric.n_compute_arrays
        out.append(sp)
    return out


def _record_conversions(cim: CiMConfig, conversions: int) -> None:
    """Count ``conversions`` analytic ADC conversions, and those among them
    whose converter takes ``core.adc``'s direct path (bit-plane mode with a
    noiseless ADC): direct / total is the path's share."""
    obs_metrics.inc(
        "fabric_conversions_total",
        conversions,
        help="Analytic ADC conversions per executed matmul "
        "(planes x rows x k-tiles x columns).",
    )
    direct = cim.mode == "bitplane" and takes_direct_path(cim.adc_config())
    obs_metrics.inc(
        "fabric_direct_conversions_total",
        conversions if direct else 0,
        help="ADC conversions resolved by the direct boundary count "
        "(noiseless comparators), not the search-tree walk.",
    )


def _chip_noise_key(key: Optional[jax.Array], chip_index):
    """Per-chip ADC noise key: ``fold_in(key, chip_index)`` for every chip
    except chip 0, which keeps the caller's key unchanged — so a 1x1 mesh
    reproduces the unsharded path's per-tile ``fold_in(key, nt)`` draws
    bit-for-bit while every other chip gets an independent stream.

    ``chip_index`` is the K-shard (model-axis) index only: chips along the
    data axis share the key and are distinguished instead by the global row
    ids threaded through ``column_tile_matmul``'s ``row_offset``, which makes
    each batch row's draws invariant to the batch size and data split — the
    property ``fabric.autotune``'s zero-padded bucketed batches rely on.

    Accepts a Python int (sequential backend) or a traced ``axis_index``
    scalar (shard_map backend); both derivations are identical, which is what
    keeps the two backends' noise draws equal.
    """
    if key is None:
        return None
    if isinstance(chip_index, int):
        return key if chip_index == 0 else jax.random.fold_in(key, chip_index)
    return jax.lax.cond(
        chip_index == 0,
        lambda: key,
        lambda: jax.random.fold_in(key, chip_index),
    )


def resolve_backend(sharded: ShardedPlacement, backend: str = "auto") -> str:
    """Resolve the execution backend for a sharded plan.

    ``shard_map`` needs (a) a concrete device mesh — ``data * model`` jax
    devices on the host — and (b) a plan with no replication fallbacks (the
    realized ``d_splits x k_splits`` must equal the mesh shape, or devices
    along a replicated axis would double-count partial sums). ``"auto"``
    falls back to ``"sequential"`` when either is missing — and also on a
    1x1 mesh, where there is nothing to parallelize and the SPMD dispatch
    is pure overhead; an explicit ``backend="shard_map"`` runs it anyway
    (the 1x1 bit-exactness tests do exactly that) or raises with the
    reasons when ineligible.

    Example::

        >>> from repro.fabric import ChipMeshConfig, FabricConfig, map_matmul, shard_placement
        >>> fb = FabricConfig(mode="pair_sar", n_arrays=8)
        >>> sp = shard_placement(map_matmul("l", 4, 64, 64, fb), ChipMeshConfig(fabric=fb))
        >>> resolve_backend(sp, "auto") in ("sequential", "shard_map")
        True
    """
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; pick from {BACKENDS}")
    if backend == "sequential":
        return "sequential"
    cm = sharded.chip_mesh
    problems = []
    n_dev = len(jax.devices())
    if n_dev < cm.n_chips:
        problems.append(
            f"host has {n_dev} jax device(s) < {cm.n_chips} chips (set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={cm.n_chips})"
        )
    if (sharded.d_splits, sharded.k_splits) != (cm.data, cm.model):
        problems.append(
            f"replication fallbacks leave realized splits "
            f"{sharded.d_splits}x{sharded.k_splits} != mesh {cm.data}x{cm.model}"
        )
    if problems:
        if backend == "shard_map":
            raise ValueError("shard_map backend unavailable: " + "; ".join(problems))
        # auto -> sequential: a real degradation, recorded as a structured
        # fallback (no-op unless repro.obs tracing/metrics are active)
        record_fallback(
            "fabric.shard", classify_fallback(problems), "; ".join(problems)
        )
        return "sequential"
    if backend == "auto" and cm.n_chips == 1:
        return "sequential"  # single chip: SPMD dispatch is pure overhead
    return "shard_map"


def _shard_map_matmul(x_int, w_int, sx, sw, sharded: ShardedPlacement, cim: CiMConfig, key):
    """One SPMD program over the concrete ``(data, model)`` device mesh.

    Each device holds its chip's batch rows and K-slice, runs the same
    per-column-tile ``core.cim_linear`` machinery as the sequential loop, and
    the digital combine over the ``model`` axis is the physical collective:
    a ``psum_scatter`` reduce-scatter (the ``(C-1) * M * N * psum_bits`` link
    traffic of ``crosschip_bits_per_pass``) followed by the gather that
    redistributes the combined rows. Scales are applied after the combine —
    the partial sums are integer-valued, so the sum is exact and the 1x1 mesh
    stays bit-for-bit equal to the unsharded path.
    """
    fabric = sharded.chip_mesh.fabric
    k_tiles = math.ceil(sharded.k / fabric.rows)
    # pad K to whole tiles so every model-axis device gets an equal block;
    # _bitplane_matmul pads the ragged tail identically in the sequential path
    k_pad = k_tiles * fabric.rows - x_int.shape[1]
    if k_pad:
        x_int = jnp.pad(x_int, ((0, 0), (0, k_pad)))
        w_int = jnp.pad(w_int, ((0, k_pad), (0, 0)))
    fn = _shard_map_program(
        sharded.d_splits, sharded.k_splits, fabric.cols, cim, key is not None
    )
    args = (x_int, w_int, sx, sw) + ((key,) if key is not None else ())
    return fn(*args)


@functools.lru_cache(maxsize=64)
def _shard_map_program(
    d_splits: int, k_splits: int, cols: int, cim: CiMConfig, has_key: bool
):
    """The jitted SPMD program of ``_shard_map_matmul`` for one mesh and CiM
    configuration, built once: an eager ``shard_map`` of a fresh closure
    would trace and dispatch every primitive on every call."""
    mesh = make_chip_mesh(d_splits, k_splits, require_concrete=True)

    def chip_fn(x_blk, w_blk, sx_, sw_, *maybe_key):
        di = jax.lax.axis_index("data")
        ci = jax.lax.axis_index("model")
        # the chip key carries only the K-shard index: data-axis chips are
        # told apart by the global ROW ids they pass down (row_offset), so a
        # row's draws do not move when the batch split changes
        chip_key = _chip_noise_key(maybe_key[0], ci) if has_key else None
        # this chip's K-partial, (m_shard, N) — the one shared inner loop
        y_local, st = column_tile_matmul(
            x_blk, w_blk, cim, cols, key=chip_key,
            row_offset=di * x_blk.shape[0],
        )
        conversions, comparisons = st.conversions, st.comparisons
        if k_splits > 1:
            if w_blk.shape[1] % k_splits == 0:
                # the modeled ring reduce-scatter, then the gather that hands
                # every chip the combined rows back
                y_sc = jax.lax.psum_scatter(
                    y_local, "model", scatter_dimension=1, tiled=True
                )
                y_sum = jax.lax.all_gather(y_sc, "model", axis=1, tiled=True)
            else:
                y_sum = jax.lax.psum(y_local, "model")
        else:
            y_sum = y_local
        conversions = jax.lax.psum(conversions, ("data", "model"))
        comparisons = jax.lax.psum(comparisons, ("data", "model"))
        return y_sum * sx_ * sw_, conversions, comparisons

    in_specs = [P("data", "model"), P("model", None), P(), P(None, None)]
    if has_key:
        in_specs.append(P())
    return jax.jit(
        jax.shard_map(
            chip_fn,
            mesh=mesh,
            in_specs=tuple(in_specs),
            out_specs=(P("data", None), P(), P()),
            check_vma=False,
        )
    )


def execute_sharded_matmul(
    x: jnp.ndarray,
    w: jnp.ndarray,
    chip_mesh: ChipMeshConfig,
    cim: CiMConfig,
    sharded: Optional[ShardedPlacement] = None,
    key: Optional[jax.Array] = None,
    return_stats: bool = False,
    backend: str = "auto",
):
    """``y = x @ w`` executed shard-wise over the chip mesh.

    Quantization scales are global (fabric-level calibration), so every chip
    computes integer partial product-sums over its own K-slice and the
    reduce-scatter combine is a plain digital sum — on a 1x1 mesh the
    operation sequence is identical to ``fabric.execute.execute_matmul`` and
    the result is bit-for-bit equal (bitplane and fake_quant, noiseless ADC).

    ``backend`` selects how the chips run (see :func:`resolve_backend`):
    ``"sequential"`` simulates them in a host loop, ``"shard_map"`` places
    them on a real jax device mesh and combines partials with the
    ``psum_scatter`` reduce-scatter the traffic model prices, ``"auto"``
    (default) uses shard_map when the host has the devices and the plan has
    no fallbacks. The two backends draw identical per-chip ADC noise keys
    (:func:`_chip_noise_key`), so they agree to float tolerance on any mesh
    and bit-for-bit on 1x1.

    ``x``: (..., K); ``w``: (K, N). Per-chip shards run through the same
    ``core.cim_linear`` per-plane machinery as the single-chip path; the
    Pallas kernel path is not used here because it re-derives quantization
    scales per call, which would differ per K-slice.

    Example::

        >>> import jax, jax.numpy as jnp
        >>> from repro.core.cim_linear import CiMConfig
        >>> from repro.fabric import ChipMeshConfig, FabricConfig, execute_sharded_matmul
        >>> cm = ChipMeshConfig(model=2, fabric=FabricConfig(mode="pair_sar", n_arrays=8))
        >>> cim = CiMConfig(mode="bitplane", a_bits=4, w_bits=4, adc_bits=5, rows=16, ste=False)
        >>> x = jax.random.normal(jax.random.PRNGKey(0), (4, 64))
        >>> w = jax.random.normal(jax.random.PRNGKey(1), (64, 32))
        >>> execute_sharded_matmul(x, w, cm, cim).shape
        (4, 32)
    """
    if cim.mode not in ("bitplane", "fake_quant"):
        raise ValueError(f"fabric execution needs bitplane|fake_quant, got {cim.mode!r}")
    fabric = chip_mesh.fabric
    batch_shape = x.shape[:-1]
    k = x.shape[-1]
    n = w.shape[1]
    xm = x.reshape(-1, k)
    if sharded is None:
        base = map_matmul("matmul", xm.shape[0], k, n, fabric, cim=cim)
        sharded = shard_placement(base, chip_mesh)
    if sharded.chip_mesh != chip_mesh:
        raise ValueError("sharded placement was planned on a different ChipMeshConfig")
    if (sharded.k, sharded.n) != (k, n):
        raise ValueError(
            f"sharded placement is for K={sharded.k},N={sharded.n}; got K={k},N={n}"
        )
    requested = backend
    backend = resolve_backend(sharded, backend)
    if backend == "shard_map" and xm.shape[0] % sharded.d_splits:
        # the plan was made for a divisible batch; a ragged runtime batch can
        # only run on the sequential loop (last shard takes the remainder)
        if requested == "shard_map":
            raise ValueError(
                f"shard_map backend unavailable: batch rows {xm.shape[0]} are "
                f"not divisible by the data axis ({sharded.d_splits})"
            )
        record_fallback(
            "fabric.shard", REASON_RAGGED_BATCH,
            f"batch rows {xm.shape[0]} % data axis {sharded.d_splits} != 0",
        )
        backend = "sequential"
    if obs_metrics.active():
        # host-side analytic accounting only: the sharded chips jointly
        # perform the same planes x rows x k-tiles x columns of conversions
        # as the unsharded op, and the link bits are the placement's
        # (C-1) * M * N * psum_bits reduce-scatter traffic
        obs_metrics.inc("fabric_matmuls_total", help="Mapped matmuls executed.")
        _record_conversions(
            cim, cim.a_bits * cim.w_bits * xm.shape[0] * math.ceil(k / fabric.rows) * n
        )
        obs_metrics.inc(
            "fabric_link_bits_total",
            sharded.crosschip_bits_per_pass,
            help="Cross-chip reduce-scatter bits moved per executed matmul.",
        )
    span = obs_trace.span(
        "fabric.shard.matmul",
        layer=sharded.name, m=xm.shape[0], k=k, n=n,
        backend=backend, mesh=f"{sharded.d_splits}x{sharded.k_splits}",
    )
    k_splits, d_splits = sharded.k_splits, sharded.d_splits
    k_tiles = math.ceil(k / fabric.rows)
    cols = fabric.cols

    with span:
        # fabric-level quantization: global scales, exactly the unsharded
        # front-end
        x_int, sx = quantize_symmetric(xm, cim.a_bits, cim.a_signed)
        w_int, sw = quantize_symmetric(w, cim.w_bits, cim.w_signed, per_axis=-1)

        if backend == "shard_map":
            y_q, conversions, comparisons = _shard_map_matmul(
                x_int, w_int, sx, sw, sharded, cim, key
            )
        else:
            m_total = xm.shape[0]
            m_shard = m_total // d_splits if d_splits > 1 else m_total
            conversions = jnp.zeros((), jnp.int32)
            comparisons = jnp.zeros((), jnp.int32)
            data_parts = []
            for d in range(d_splits):
                m0 = d * m_shard
                m1 = (d + 1) * m_shard if d < d_splits - 1 else m_total
                x_d = x_int[m0:m1]
                total = None
                for c in range(k_splits):
                    k0, k1 = _k_slice(k, fabric.rows, k_tiles, k_splits, c)
                    chip_key = _chip_noise_key(key, c)
                    y_c, st = column_tile_matmul(
                        x_d[:, k0:k1], w_int[k0:k1], cim, cols,
                        key=chip_key, row_offset=m0,
                    )
                    conversions = conversions + st.conversions
                    comparisons = comparisons + st.comparisons
                    # digital partial-sum combine == the reduce-scatter's sum
                    total = y_c if total is None else total + y_c
                data_parts.append(total * sx * sw)
            y_q = jnp.concatenate(data_parts, axis=0)

        if cim.ste:
            y_lin = xm @ w
            y_q = y_lin + jax.lax.stop_gradient(y_q - y_lin)

    y = y_q.reshape(*batch_shape, n)
    if return_stats:
        return y, CimStats(conversions, comparisons)
    return y
