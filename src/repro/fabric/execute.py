"""Numerically execute a mapped placement — batched, tile by tile.

The mapped path must be *bit-for-bit* the unmapped op: quantization scales
are computed once at the fabric level (per-tensor activations, per-column
weights — exactly ``core.cim_linear.cim_matmul``'s front-end), then every
output-column tile runs through the same per-plane machinery:

  * ``bitplane``   — ``core.cim_linear`` faithful per-plane path per tile
                     (noiseless memory-immersed ADC -> exact integer matmul
                     whenever ``2^adc_bits >= 2*rows``, as on the test chip);
  * ``fake_quant`` — the fused Pallas kernel (``kernels.ops.cim_matmul_op``)
                     per tile, interpret-mode on CPU.

K-tiling at ``rows`` boundaries happens *inside* the per-tile op and lands on
the same reduction slices the placement assigns to individual arrays, so the
per-array partial sums are the ones actually accumulated. Exact equality with
the unmapped op holds for the noiseless ADC; with comparator noise the mapped
run draws per-tile keys and matches only in distribution.

The per-column-tile inner loop itself lives in ``fabric.tiles`` — the single
definition shared with ``fabric.shard`` (both backends) and the fused
whole-model program (``fabric.program``).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.cim_linear import (
    CimStats,
    CiMConfig,
    quantize_symmetric,
)
from repro.fabric.mapper import LayerPlacement, map_matmul
from repro.fabric.tiles import analytic_cim_stats, column_tile_matmul
from repro.fabric.topology import FabricConfig

__all__ = ["execute_matmul", "execute_linear"]


def execute_matmul(
    x: jnp.ndarray,
    w: jnp.ndarray,
    fabric: FabricConfig,
    cim: CiMConfig,
    placement: Optional[LayerPlacement] = None,
    key: Optional[jax.Array] = None,
    return_stats: bool = False,
    use_kernel: bool = True,
):
    """``y = x @ w`` executed tile-wise over the mapped fabric placement.

    ``x``: (..., K); ``w``: (K, N). Matches ``cim_matmul(x, w, cim)``
    bit-for-bit in both ``bitplane`` and ``fake_quant`` modes (noiseless ADC).

    ``return_stats=True`` is meaningful in both modes: ``bitplane`` counts
    the conversions/comparisons actually performed; ``fake_quant`` (kernel or
    surrogate path) counts them analytically — tiles x plane-pairs x columns
    (``fabric.tiles.analytic_cim_stats``).

    Example::

        >>> import jax
        >>> from repro.core.cim_linear import CiMConfig
        >>> from repro.fabric import FabricConfig, execute_matmul
        >>> fb = FabricConfig(mode="hybrid", n_arrays=12)
        >>> cim = CiMConfig(mode="bitplane", a_bits=4, w_bits=4, adc_bits=5, rows=16, ste=False)
        >>> x = jax.random.normal(jax.random.PRNGKey(0), (2, 40))
        >>> w = jax.random.normal(jax.random.PRNGKey(1), (40, 70))
        >>> execute_matmul(x, w, fb, cim).shape
        (2, 70)
    """
    if cim.mode not in ("bitplane", "fake_quant"):
        raise ValueError(f"fabric execution needs bitplane|fake_quant, got {cim.mode!r}")
    batch_shape = x.shape[:-1]
    k = x.shape[-1]
    n = w.shape[1]
    xm = x.reshape(-1, k)
    if placement is None:
        placement = map_matmul("matmul", xm.shape[0], k, n, fabric, cim=cim)
    if (placement.k, placement.n) != (k, n):
        raise ValueError(
            f"placement is for K={placement.k},N={placement.n}; got K={k},N={n}"
        )

    # fabric-level quantization: identical to the unmapped op's front-end
    x_int, sx = quantize_symmetric(xm, cim.a_bits, cim.a_signed)
    w_int, sw = quantize_symmetric(w, cim.w_bits, cim.w_signed, per_axis=-1)

    cols = fabric.cols
    if cim.mode == "fake_quant" and use_kernel:
        from repro.kernels.ops import cim_matmul_op

        # the fused kernel re-derives the same per-tensor / per-column
        # scales from the float operands and applies them itself
        parts = []
        for nt in range(placement.n_tiles):
            n0, n1 = nt * cols, min((nt + 1) * cols, n)
            parts.append(
                cim_matmul_op(
                    xm,
                    w[:, n0:n1],
                    rows=cim.rows,
                    adc_bits=cim.adc_bits,
                    mode="fake_quant",
                    a_bits=cim.a_bits,
                    w_bits=cim.w_bits,
                    a_signed=cim.a_signed,
                    w_signed=cim.w_signed,
                )
            )
        y_q = jnp.concatenate(parts, axis=1)
        # the kernel path performs the same tiles x plane-pairs x columns of
        # conversions as the faithful path — count them analytically
        stats = analytic_cim_stats(cim, xm.shape[0], placement.k_tiles, n)
        conversions, comparisons = stats.conversions, stats.comparisons
    else:
        y_int, stats = column_tile_matmul(x_int, w_int, cim, cols, key=key)
        conversions, comparisons = stats.conversions, stats.comparisons
        y_q = y_int * sx * sw

    if cim.ste:
        y_lin = xm @ w
        y_q = y_lin + jax.lax.stop_gradient(y_q - y_lin)

    y = y_q.reshape(*batch_shape, n)
    if return_stats:
        return y, CimStats(conversions, comparisons)
    return y


def execute_linear(
    x: jnp.ndarray,
    w: jnp.ndarray,
    bias: Optional[jnp.ndarray] = None,
    fabric: Optional[FabricConfig] = None,
    cim: Optional[CiMConfig] = None,
    placement: Optional[LayerPlacement] = None,
    key: Optional[jax.Array] = None,
):
    """Mapped counterpart of ``core.cim_linear.cim_linear``.

    Example::

        >>> import jax, jax.numpy as jnp
        >>> from repro.fabric import execute_linear
        >>> x = jax.random.normal(jax.random.PRNGKey(0), (4, 48))
        >>> w = jax.random.normal(jax.random.PRNGKey(1), (48, 40))
        >>> execute_linear(x, w, bias=jnp.zeros((40,))).shape
        (4, 40)
    """
    if fabric is None:
        fabric = FabricConfig()
    if cim is None:
        cim = CiMConfig(mode="bitplane", adc_bits=fabric.adc_bits, rows=fabric.rows, ste=False)
    y = execute_matmul(x, w, fabric, cim, placement=placement, key=key)
    if bias is not None:
        y = y + bias
    return y
