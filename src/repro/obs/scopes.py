"""The closed vocabulary of device scopes: one ``jax.named_scope`` name per
layer of the model, the CiM linear and the fabric graph.

A scope is metadata only. It adds no jaxpr equation and no HLO instruction;
it prefixes the ``op_name`` of every op traced inside it, which the profiler
shows on each device op. An op belongs to the innermost vocabulary name on
its ``op_name`` path, so nested scopes split their parent's time: the
``cim_linear`` ops inside ``attention`` are the linear's, not attention's,
and ``cim.*`` sub-scopes split ``cim_linear``.

Each name is defined here once; the model, the CiM op and the fabric import
the constant.
"""

from __future__ import annotations

__all__ = [
    "EMBED",
    "NORM",
    "ATTENTION",
    "KV_CACHE",
    "MLP",
    "CIM_LINEAR",
    "LINEAR",
    "CIM_QUANTIZE",
    "CIM_TILES",
    "CIM_STE",
    "CIM_MAC",
    "CIM_ADC",
    "LM_HEAD",
    "LAYER_SCAN",
    "OPTIMIZER",
    "FABRIC_REQUANT",
    "FABRIC_OPS",
    "fabric_op",
    "SCOPES",
]

EMBED = "embed"  # token embedding lookup
NORM = "norm"  # RMSNorm
ATTENTION = "attention"  # rope, scores, softmax, mixing; its linears are CIM_LINEAR
KV_CACHE = "kv_cache"  # KV-cache allocation and writes
MLP = "mlp"  # the SwiGLU gate; its linears are CIM_LINEAR
CIM_LINEAR = "cim_linear"  # a linear on the CiM path
LINEAR = "linear"  # a linear off the CiM path
CIM_QUANTIZE = "cim.quantize"  # activation and weight quantization
CIM_TILES = "cim.tiles"  # fake-quant partial sums per row tile, rounded and summed
CIM_STE = "cim.ste"  # the straight-through estimator's float matmul
CIM_MAC = "cim.mac"  # bit-plane analog multiply-accumulate
CIM_ADC = "cim.adc"  # bit-plane conversion: ladder, noise keys, search
LM_HEAD = "lm_head"  # unembedding: decode logits and the training loss
LAYER_SCAN = "layer_scan"  # the scan over stacked layers: slicing and stacking
OPTIMIZER = "optimizer"  # the AdamW update
FABRIC_REQUANT = "fabric.requant"  # a fabric matmul node's input re-quantization

# fabric graph node ops (repro.fabric.graph); each node runs under fabric.<op>
FABRIC_OPS = ("matmul", "norm", "attention", "silu_gate", "residual", "moe_gate")


def fabric_op(op: str) -> str:
    """The scope of a fabric graph node of kind ``op``.

    Example::

        >>> fabric_op("matmul")
        'fabric.matmul'
    """
    if op not in FABRIC_OPS:
        raise ValueError(f"unknown fabric op {op!r}")
    return f"fabric.{op}"


SCOPES = (
    EMBED, NORM, ATTENTION, KV_CACHE, MLP, CIM_LINEAR, LINEAR,
    CIM_QUANTIZE, CIM_TILES, CIM_STE, CIM_MAC, CIM_ADC,
    LM_HEAD, LAYER_SCAN, OPTIMIZER, FABRIC_REQUANT,
    *(fabric_op(op) for op in FABRIC_OPS),
)
