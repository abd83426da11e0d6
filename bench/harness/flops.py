"""Model FLOPs from a configuration's shapes (HF-style keys), kept with the
benchmark so that every PR counts them the same way.

Counted: the multiply-adds of every linear, of attention's scores and values
over the causal context each token really attends to, and of the LM head for
the tokens whose logits are computed. Not counted: norms, RoPE, softmax, the
embedding gather, quantization arithmetic and recomputation.
"""

from __future__ import annotations


def _dims(conf: dict):
    d = conf["hidden_size"]
    h = conf["num_attention_heads"]
    kv = conf["num_key_value_heads"]
    hd = conf.get("head_dim", d // h)
    return d, h, kv, hd, conf["intermediate_size"], conf["num_hidden_layers"]


def linear_params_per_layer(conf: dict) -> int:
    """Weights of one block's linears (q, k, v, o, gate, up, down)."""
    d, h, kv, hd, f, _ = _dims(conf)
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f


def n_params(conf: dict) -> int:
    """Every parameter: embeddings (once when tied), blocks with their two
    norms, and the final norm."""
    d, *_, n_layers = _dims(conf)
    emb = conf["vocab_size"] * d * (1 if conf.get("tie_word_embeddings") else 2)
    return emb + n_layers * (linear_params_per_layer(conf) + 2 * d) + d


def forward_flops(conf: dict, tokens: int, attended: int, head_tokens: int) -> float:
    """FLOPs of a forward over ``tokens`` tokens that attend to ``attended``
    keys in all (summed over tokens), with LM-head logits for
    ``head_tokens`` of them."""
    d, h, _, hd, _, n_layers = _dims(conf)
    linear = 2 * linear_params_per_layer(conf) * tokens
    attention = 4 * h * hd * attended  # q.k and p.v per key and query head
    head = 2 * d * conf["vocab_size"] * head_tokens
    return float(n_layers * (linear + attention) + head)


def serve_batch_flops(conf: dict, batch: int, prompt: int, gen: int) -> float:
    """One static batch: a prefill of ``prompt`` tokens (logits of the last
    position only), then ``gen - 1`` cached decode steps."""
    prefill = forward_flops(conf, batch * prompt, batch * prompt * (prompt + 1) // 2, batch)
    steps = gen - 1
    # the decode step at position p attends to p + 1 keys
    attended = batch * sum(p + 1 for p in range(prompt, prompt + steps))
    decode = forward_flops(conf, batch * steps, attended, batch * steps)
    return prefill + decode


def train_flops_per_token(conf: dict) -> float:
    """6 N: forward and backward per trained token, attention left out."""
    return 6.0 * n_params(conf)
