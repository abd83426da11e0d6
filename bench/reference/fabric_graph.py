"""Plain reference of the fabric's fused transformer graph: float32,
``jax.numpy`` only, nothing imported from the program under test.

The graph the fabric simulates, per block: RMS norm (``1 + scale``), the
q/k/v linears from the norm's output, RoPE-free causal attention with
grouped KV heads, the o linear, a residual add, RMS norm, gate and up
linears, SiLU(gate) * up, the down linear, a residual add; after the blocks
a final norm and the unembed linear. Every linear runs on bit-plane CiM
arrays whose ADC resolves each plane's count exactly (``2**adc_bits >=
2 * rows``), so a linear is the integer product of its quantized operands:
activations symmetric per tensor to ``a_bits``, weights per output column
to ``w_bits``, both rescaled after the sum. Inputs are embeddings
``(B, S, d)``; the output is logits ``(B, S, vocab)``.

The weights are the benchmark's own, drawn from the seed by
:func:`make_weights` and handed to the program and the reference alike,
keyed by the graph's node names.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
BLOCK_LINEARS = ("q_proj", "k_proj", "v_proj", "o_proj", "gate_proj", "up_proj", "down_proj")


def padded_vocab(conf) -> int:
    m = conf.get("pad_vocab_multiple", 256)
    return -(-conf["vocab_size"] // m) * m


def shapes(conf) -> dict:
    """Weight shapes keyed by node name; block weights carry a leading
    layer axis."""
    d, f, n = conf["hidden_size"], conf["intermediate_size"], conf["num_hidden_layers"]
    h, kv, hd = conf["num_attention_heads"], conf["num_key_value_heads"], conf["head_dim"]
    kn = {"q_proj": (d, h * hd), "k_proj": (d, kv * hd), "v_proj": (d, kv * hd),
          "o_proj": (h * hd, d), "gate_proj": (d, f), "up_proj": (d, f), "down_proj": (f, d)}
    out = {f"block.{k}": (n, *v) for k, v in kn.items()}
    out.update({"block.ln1": (n, d), "block.ln2": (n, d), "ln_f": (d,),
                "unembed": (d, padded_vocab(conf))})
    return out


@functools.partial(jax.jit, static_argnums=(0,))
def _make(shape_items, key):
    out = {}
    for i, (name, shape) in enumerate(shape_items):
        k = jax.random.fold_in(key, i)
        if len(shape) >= 2 and not name.endswith(("ln1", "ln2")):
            out[name] = jax.random.normal(k, shape, F32) / np.sqrt(shape[-2])
        else:
            out[name] = 0.1 * jax.random.normal(k, shape, F32)
    return out


def make_weights(conf: dict, seed: int) -> dict:
    """Matmul weights normal over sqrt(fan-in), norm scales 0.1 normal, in
    one jitted call on the device."""
    return _make(tuple(sorted(shapes(conf).items())), jax.random.PRNGKey(seed))


def make_inputs(conf: dict, seed: int, n: int, batch: int, seq: int):
    """``n`` input embeddings ``(batch, seq, d)`` stacked on a leading axis."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 1)
    return jax.random.normal(key, (n, batch, seq, conf["hidden_size"]), F32)


def _quantize(a, bits, axis):
    qmax = (1 << (bits - 1)) - 1
    absmax = jnp.max(jnp.abs(a), axis=axis, keepdims=axis is not None)
    scale = jnp.where(absmax > 0, absmax / qmax, 1.0)
    return jnp.clip(jnp.round(a / scale), -qmax - 1, qmax), scale


def qlinear(h, w, cim, rnd):
    x_int, sx = _quantize(h, cim["a_bits"], None)
    w_int, sw = _quantize(w, cim["w_bits"], 0)
    return rnd(jnp.einsum("bsk,kn->bsn", x_int, w_int) * sx * sw)


def rms_norm(h, scale, eps, rnd):
    inv = jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True) + eps)
    return rnd(h * inv * (1.0 + scale))


def attention(q, k, v, conf, rnd):
    b, s, _ = q.shape
    h, kv, hd = conf["num_attention_heads"], conf["num_key_value_heads"], conf["head_dim"]
    qh = q.reshape(b, s, kv, h // kv, hd)
    kh, vh = k.reshape(b, s, kv, hd), v.reshape(b, s, kv, hd)
    scores = jnp.einsum("bqkgd,bckd->bkgqc", qh, kh) / np.sqrt(hd)
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    p = rnd(jax.nn.softmax(scores, axis=-1))
    return rnd(jnp.einsum("bkgqc,bckd->bqkgd", p, vh).reshape(b, s, h * hd))


def rounding(precision: str):
    if precision == "float32":
        return lambda a: a
    dt = jnp.dtype(precision)
    return lambda a: a.astype(dt).astype(F32)


def forward(conf: dict, weights: dict, x, precision: str = "float32"):
    """Logits ``(B, S, vocab)`` of the graph for embeddings ``x``."""
    rnd = rounding(precision)
    cim, eps = conf["cim"], conf["rms_norm_eps"]
    w = {k: rnd(v) for k, v in weights.items()}
    h = rnd(x)
    for i in range(conf["num_hidden_layers"]):
        lw = {k.split(".", 1)[1]: v[i] for k, v in w.items() if k.startswith("block.")}
        n1 = rms_norm(h, lw["ln1"], eps, rnd)
        q, k, v = (qlinear(n1, lw[p], cim, rnd) for p in ("q_proj", "k_proj", "v_proj"))
        h = rnd(h + qlinear(attention(q, k, v, conf, rnd), lw["o_proj"], cim, rnd))
        n2 = rms_norm(h, lw["ln2"], eps, rnd)
        g, u = qlinear(n2, lw["gate_proj"], cim, rnd), qlinear(n2, lw["up_proj"], cim, rnd)
        h = rnd(h + qlinear(rnd(jax.nn.silu(g) * u), lw["down_proj"], cim, rnd))
    return qlinear(rms_norm(h, w["ln_f"], eps, rnd), w["unembed"], cim, rnd)
