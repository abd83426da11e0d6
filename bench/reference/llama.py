"""Plain reference of a Llama-style decoder (SmolLM) whose linears run
through the paper's CiM fake-quant surrogate: float32, ``jax.numpy`` only,
nothing imported from the program under test.

Semantics the program states and the reference follows:

* weights: the draws the program's initializer makes from ``PRNGKey(seed)``
  (:func:`init_weights` repeats its calls; norm scales start at zero and the
  norm multiplies by ``1 + scale``);
* every block linear: activations quantized symmetrically per tensor to
  ``a_bits``, weights per output column to ``w_bits``; the reduction is cut
  into tiles of ``rows``; each tile's integer partial sum is rounded to the
  ADC step ``(rows / 2**adc_bits) * rms`` (``rms`` the RMS combination of the
  bit-plane weights) and the tiles are summed;
* a static batch is served lock-step, so a linear's activation scale is taken
  over what one call sees: every prompt token of the batch in the prefill,
  and the batch's one token per decode step. A teacher-forced forward over
  prompt and served tokens reproduces that with one scale per *group*: the
  prompt positions, then each later position on its own;
* RoPE on the two halves of each head, causal softmax attention with grouped
  KV heads, SwiGLU MLP, tied LM head in full precision.

``rnd`` rounds every intermediate to a lower precision; with ``float32`` it
is the identity. The control of a cell is this reference with ``rnd`` set to
the precision below the one the configuration states.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def dims(conf):
    d = conf["hidden_size"]
    h = conf["num_attention_heads"]
    return d, h, conf["num_key_value_heads"], conf.get("head_dim", d // h)


def padded_vocab(conf) -> int:
    m = conf.get("pad_vocab_multiple", 256)
    return -(-conf["vocab_size"] // m) * m


def init_weights(conf: dict, seed: int):
    """``(tok (V, d), layers {name: (L, K, N)})`` in float32: the program's
    draws, repeated call for call (eagerly, as the program makes them, so
    that every bit agrees)."""
    key = jax.random.PRNGKey(seed)
    d, h, kv, hd = dims(conf)
    f, n_layers = conf["intermediate_size"], conf["num_hidden_layers"]
    dt = jnp.dtype(conf["torch_dtype"])
    k_embed, k_attn, k_mlp, _ = jax.random.split(key, 4)
    k_tok, _ = jax.random.split(k_embed)
    tok = jax.random.normal(k_tok, (padded_vocab(conf), d), dt) * 0.02
    ka = jax.random.split(k_attn, 4)
    s = lambda fan_in: 1.0 / np.sqrt(fan_in)
    wq = jax.random.normal(ka[0], (n_layers, d, h * hd), dt) * s(d)
    wk = jax.random.normal(ka[1], (n_layers, d, kv * hd), dt) * s(d)
    wv = jax.random.normal(ka[2], (n_layers, d, kv * hd), dt) * s(d)
    wo = jax.random.normal(ka[3], (n_layers, h * hd, d), dt) * s(h * hd)
    km = jax.random.split(k_mlp, 3)
    w_gate = jax.random.normal(km[0], (n_layers, d, f), dt) / np.sqrt(d)
    w_up = jax.random.normal(km[1], (n_layers, d, f), dt) / np.sqrt(d)
    w_down = jax.random.normal(km[2], (n_layers, f, d), dt) / np.sqrt(f)
    layers = dict(wq=wq, wk=wk, wv=wv, wo=wo, w_gate=w_gate, w_up=w_up, w_down=w_down)
    return tok.astype(F32), {k: v.astype(F32) for k, v in layers.items()}


def _plane_rms(bits: int, signed: bool) -> float:
    w = [2.0**i for i in range(bits)]
    if signed:
        w[-1] = -w[-1]
    return float(np.sqrt(np.sum(np.square(w))))


def _quantize_columns(w, bits):
    qmax = (1 << (bits - 1)) - 1
    absmax = jnp.max(jnp.abs(w), axis=0, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / qmax, 1.0)
    return jnp.clip(jnp.round(w / scale), -qmax - 1, qmax), scale


def cim_linear(x, w, cim, group_of, n_groups, rnd, chunk_rows=2048):
    """``x (B, T, K) @ w (K, N)`` through the fake-quant surrogate, with one
    activation scale per position group (``group_of`` (T,) int)."""
    b, t, k = x.shape
    n = w.shape[1]
    a_bits, w_bits, rows, adc_bits = cim["a_bits"], cim["w_bits"], cim["rows"], cim["adc_bits"]
    qmax = (1 << (a_bits - 1)) - 1
    per_pos = jnp.max(jnp.abs(x), axis=(0, 2))  # (T,)
    per_group = jax.ops.segment_max(per_pos, group_of, num_segments=n_groups)
    absmax = per_group[group_of]
    scale = rnd(jnp.where(absmax > 0, absmax / qmax, 1.0))  # (T,)
    x_int = jnp.clip(jnp.round(x / scale[None, :, None]), -qmax - 1, qmax)
    w_int, sw = _quantize_columns(w, w_bits)
    pad = (-k) % rows
    tiles = (k + pad) // rows
    xt = jnp.pad(x_int.reshape(b * t, k), ((0, 0), (0, pad)))
    wt = jnp.pad(w_int, ((0, pad), (0, 0))).reshape(tiles, rows, n)
    # adc_bits None: the partial sums are not rounded (a planted fault)
    step = None if adc_bits is None else (
        (rows / (1 << adc_bits)) * _plane_rms(a_bits, True) * _plane_rms(w_bits, True))
    m = b * t
    cr = min(chunk_rows, m)
    mp = -(-m // cr) * cr
    xc = jnp.pad(xt, ((0, mp - m), (0, 0))).reshape(mp // cr, cr, tiles, rows)

    def tile_sums(xi):
        # integers below 2**8 are exact in one bfloat16 pass and their
        # 16-term sums in the float32 accumulator: the partial sums are exact
        # at the default precision, on any backend
        part = jnp.einsum("mtr,trn->mtn", xi, wt, precision=jax.lax.Precision.DEFAULT,
                          preferred_element_type=F32)
        return jnp.sum(part if step is None else jnp.round(part / step) * step, axis=1)

    y_int = jax.lax.map(tile_sums, xc).reshape(mp, n)[:m].reshape(b, t, n)
    return rnd(y_int * scale[None, :, None] * sw)


def rms_norm(x, eps, rnd, scale=0.0):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return rnd(x * jax.lax.rsqrt(var + eps) * (1.0 + scale))


def rope(x, theta):
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, hd, 2) / hd))
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * jnp.asarray(freqs, F32)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def block(conf, x, lw, group_of, n_groups, rnd):
    """One decoder block on the residual stream ``x (B, T, d)``."""
    d, h, kv, hd = dims(conf)
    b, t, _ = x.shape
    cim, eps = conf["cim"], conf["rms_norm_eps"]
    lin = lambda a, w: cim_linear(a, w, cim, group_of, n_groups, rnd)
    hn = rms_norm(x, eps, rnd)
    q = rope(lin(hn, lw["wq"]).reshape(b, t, h, hd), conf["rope_theta"])
    k = rope(lin(hn, lw["wk"]).reshape(b, t, kv, hd), conf["rope_theta"])
    v = lin(hn, lw["wv"]).reshape(b, t, kv, hd)
    q = rnd(q).reshape(b, t, kv, h // kv, hd) / np.sqrt(hd)
    k = rnd(k)
    scores = jnp.einsum("bqkgd,bckd->bkgqc", q, k)
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    p = rnd(jax.nn.softmax(scores, axis=-1))
    att = rnd(jnp.einsum("bkgqc,bckd->bqkgd", p, v)).reshape(b, t, h * hd)
    x = rnd(x + lin(att, lw["wo"]))
    hn = rms_norm(x, eps, rnd)
    gate, up = lin(hn, lw["w_gate"]), lin(hn, lw["w_up"])
    return rnd(x + lin(rnd(jax.nn.silu(gate) * up), lw["w_down"]))


def rounding(precision: str):
    """Round-trip through ``precision``; identity for float32."""
    if precision == "float32":
        return lambda a: a
    dt = jnp.dtype(precision)
    return lambda a: a.astype(dt).astype(F32)


@functools.partial(jax.jit, static_argnums=(0, 4, 5, 6))
def _block_jit(conf_key, x, lw, group_of, cim_items, n_groups, precision):
    conf = dict(conf_key)
    conf["cim"] = dict(cim_items)
    return block(conf, x, lw, group_of, n_groups, rounding(precision))


@functools.partial(jax.jit, static_argnums=(4, 5))
def _head(h, tok, targets, eps, vocab, precision):
    """Per row: the largest logit minus the logit of each target token,
    and the argmax, at every position of ``h (B, G, d)``."""
    rnd = rounding(precision)
    hn = rms_norm(h, eps, rnd)

    def row(args):
        hr, tr = args
        logits = (hr @ rnd(tok).T)[:, :vocab]  # (G, V)
        best = jnp.max(logits, axis=-1)
        picked = jnp.take_along_axis(logits, tr, axis=-1)  # (G, n_targets)
        return best[:, None] - picked, jnp.argmax(logits, axis=-1)

    return jax.lax.map(row, (hn, targets))


def served_gaps(conf, seed, tokens, n_prompt, targets, precision="float32"):
    """Teacher-forced forward over ``tokens (B, T)``: prompts of
    ``n_prompt`` tokens followed by served tokens. For every position from
    the last prompt token on (``G = T - n_prompt + 1`` of them), returns
    ``(gaps (B, G, n_targets), argmax (B, G))``: how far below the best
    logit each of ``targets (B, G, n_targets)`` lies, and the token this
    precision puts first."""
    with jax.default_matmul_precision("highest"):
        tok, layers = init_weights(conf, seed)
        rnd = rounding(precision)
        b, t = tokens.shape
        pos = np.arange(t)
        group_of = jnp.asarray(np.where(pos < n_prompt, 0, pos - n_prompt + 1), jnp.int32)
        n_groups = t - n_prompt + 1
        x = rnd(jnp.take(rnd(tok), jnp.asarray(tokens), axis=0))
        conf_key = tuple(sorted((k, v) for k, v in conf.items() if not isinstance(v, (dict, list))))
        cim_items = tuple(sorted(conf["cim"].items()))
        for i in range(conf["num_hidden_layers"]):
            lw = {k: v[i] for k, v in layers.items()}
            x = _block_jit(conf_key, x, lw, group_of, cim_items, n_groups, precision)
        h = x[:, n_prompt - 1:]
        gaps, argmax = _head(h, tok, jnp.asarray(targets, jnp.int32), conf["rms_norm_eps"],
                             conf["vocab_size"], precision)
        return np.asarray(gaps), np.asarray(argmax)


# ---------------------------------------------------------------------------
# Training: the loss, its gradient under the straight-through estimator, and
# AdamW
# ---------------------------------------------------------------------------


def _ste_linear(x, w, cim, rnd):
    """Quantized forward (the whole batch is one call, so one activation
    scale), the gradient of ``x @ w`` backward (QAT)."""
    group_of = jnp.zeros((x.shape[1],), jnp.int32)
    y_lin = jnp.einsum("btk,kn->btn", x, w)
    y_q = cim_linear(x, w, cim, group_of, 1, rnd)
    return rnd(y_lin + jax.lax.stop_gradient(y_q - y_lin))


def _train_block(conf, x, lw, rnd):
    d, h, kv, hd = dims(conf)
    b, t, _ = x.shape
    cim, eps, theta = conf["cim"], conf["rms_norm_eps"], conf["rope_theta"]
    lin = lambda a, w: _ste_linear(a, w, cim, rnd)
    hn = rms_norm(x, eps, rnd, lw["ln1"])
    q = rnd(rope(lin(hn, lw["wq"]).reshape(b, t, h, hd), theta))
    k = rnd(rope(lin(hn, lw["wk"]).reshape(b, t, kv, hd), theta))
    v = lin(hn, lw["wv"]).reshape(b, t, kv, hd)
    q = q.reshape(b, t, kv, h // kv, hd) / np.sqrt(hd)
    scores = jnp.einsum("bqkgd,bckd->bkgqc", q, k)
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    p = rnd(jax.nn.softmax(scores, axis=-1))
    att = rnd(jnp.einsum("bkgqc,bckd->bqkgd", p, v)).reshape(b, t, h * hd)
    x = rnd(x + lin(att, lw["wo"]))
    hn = rms_norm(x, eps, rnd, lw["ln2"])
    mid = rnd(jax.nn.silu(lin(hn, lw["w_gate"])) * lin(hn, lw["w_up"]))
    return rnd(x + lin(mid, lw["w_down"]))


def train_params(conf: dict, seed: int) -> dict:
    """The program's initial parameters in float32, under its leaf names."""
    tok, layers = init_weights(conf, seed)
    n, d = conf["num_hidden_layers"], conf["hidden_size"]
    return {
        "embed": {"tok": tok},
        "attn": {k: layers[k] for k in ("wq", "wk", "wv", "wo")},
        "mlp": {k: layers[k] for k in ("w_gate", "w_up", "w_down")},
        "ln1": jnp.zeros((n, d), F32), "ln2": jnp.zeros((n, d), F32),
        "ln_f": jnp.zeros((d,), F32),
    }


def train_loss(conf: dict, params: dict, inputs, labels, precision: str = "float32"):
    """Mean next-token cross-entropy over every label; each block is
    recomputed in the backward pass so that 30 layers fit."""
    rnd = rounding(precision)
    tok = rnd(params["embed"]["tok"])
    x = rnd(jnp.take(tok, inputs, axis=0))
    layers = {**params["attn"], **params["mlp"], "ln1": params["ln1"], "ln2": params["ln2"]}

    @jax.checkpoint
    def body(x, lw):
        return _train_block(conf, x, {k: rnd(v) for k, v in lw.items()}, rnd), None

    x, _ = jax.lax.scan(body, x, layers)
    h = rms_norm(x, conf["rms_norm_eps"], rnd, params["ln_f"])

    @jax.checkpoint
    def row_loss(args):
        hr, lr = args
        logits = (hr @ tok.T)[:, :conf["vocab_size"]]
        picked = jnp.take_along_axis(logits, lr[:, None], axis=-1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked)

    return jnp.sum(jax.lax.map(row_loss, (h, labels))) / labels.size


def warmup_cosine(step, peak, warmup, total, floor=0.1):
    """Linear warm-up to ``peak``, then cosine decay to ``floor * peak``."""
    if step < warmup:
        return peak * step / max(warmup, 1)
    frac = min(max((step - warmup) / max(total - warmup, 1), 0.0), 1.0)
    return peak * (floor + (1 - floor) * 0.5 * (1 + np.cos(np.pi * frac)))


@functools.partial(jax.jit, static_argnums=(0, 4))
def _grad(conf_key, params, inputs, labels, precision):
    conf = dict(conf_key)
    conf["cim"] = dict(conf["cim"])
    return jax.value_and_grad(lambda p: train_loss(conf, p, inputs, labels, precision))(params)


def adamw_step(params, m, v, grads, count, lr, opt):
    """One AdamW step after clipping the gradient to a global norm."""
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
    grads = jax.tree.map(lambda g: g * jnp.minimum(1.0, opt["grad_clip"] / gnorm), grads)
    m = jax.tree.map(lambda m_, g: opt["b1"] * m_ + (1 - opt["b1"]) * g, m, grads)
    v = jax.tree.map(lambda v_, g: opt["b2"] * v_ + (1 - opt["b2"]) * g * g, v, grads)
    c1, c2 = 1 - opt["b1"] ** count, 1 - opt["b2"] ** count
    params = jax.tree.map(
        lambda p, m_, v_: p - lr * ((m_ / c1) / (jnp.sqrt(v_ / c2) + opt["eps"])
                                    + opt["weight_decay"] * p), params, m, v)
    return params, m, v


def train_steps(conf: dict, seed: int, batches: list, sched: dict, precision: str = "float32"):
    """Steps ``0 .. len(batches) - 1`` from the seed's initial parameters.
    Returns the losses, the first gradient (before clipping), and the
    parameters at the start and at the end."""
    with jax.default_matmul_precision("highest"):
        params = train_params(conf, seed)
        start = params
        zeros = jax.tree.map(jnp.zeros_like, params)
        m, v = zeros, zeros
        key = tuple(sorted((k, v_) for k, v_ in conf.items() if not isinstance(v_, (dict, list))))
        key += (("cim", tuple(sorted(conf["cim"].items()))),)
        losses, first_grad = [], None
        for step, (inputs, labels) in enumerate(batches):
            loss, grads = _grad(key, params, jnp.asarray(inputs), jnp.asarray(labels), precision)
            lr = warmup_cosine(step, sched["lr"], sched["warmup"], sched["steps"])
            params, m, v = adamw_step(params, m, v, grads, step + 1, lr, sched["optimizer"])
            losses.append(float(loss))
            if first_grad is None:
                first_grad = grads
        return losses, first_grad, start, params
