"""Model FLOPs the algorithms need, computed from shapes.

A multiply-add counts as 2 FLOPs.  Work a program does beyond the model's
(masked attention slots, recomputation, padding) is not counted: these are
the numerators of the ``*_mfu`` metrics.
"""
from __future__ import annotations


def mlp_params(widths) -> int:
    """Parameters of a dense MLP with biases, e.g. (784, 200, 200, 10)."""
    return sum(a * b + b for a, b in zip(widths[:-1], widths[1:]))


def mlp_train_flops_per_sample(widths) -> int:
    """Forward, backward and update of one sample: 6 FLOPs per parameter."""
    return 6 * mlp_params(widths)


def mlp_eval_flops_per_sample(widths) -> int:
    """One forward pass: 2 FLOPs per parameter."""
    return 2 * mlp_params(widths)


def decoder_matmul_params(cfg: dict) -> int:
    """Weights a token multiplies through in the decoder layers (embedding
    lookup and unembedding not included)."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * per_layer


def decoder_request_flops(cfg: dict, prompt_len: int, gen_tokens: int) -> int:
    """Forward FLOPs of one greedy request: the prompt and the ``gen_tokens - 1``
    generated tokens that are fed back pass through every layer, with causal
    attention over the positions before them; the unembedding runs once per
    generated token (the last prompt position and each fed-back token)."""
    positions = prompt_len + gen_tokens - 1
    weights = 2 * decoder_matmul_params(cfg) * positions
    # QK^T and PV: 2 * 2 * heads * head_dim FLOPs per attended position
    attended = positions * (positions + 1) // 2
    attn = 4 * cfg["num_attention_heads"] * cfg["head_dim"] * attended * cfg["num_hidden_layers"]
    unembed = 2 * cfg["hidden_size"] * cfg["vocab_size"] * gen_tokens
    return weights + attn + unembed
