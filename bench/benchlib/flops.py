"""Operations and bytes that the model's algorithm needs, from shapes.

Counts are of useful work only: a multiply-add is 2 operations; padded
vocabulary rows, idle batch slots, repeated KV heads and recomputation
are not counted. ``m`` is a configuration file's dictionary.
"""

from __future__ import annotations


def matmul_params(m: dict) -> int:
    """Weights that every token multiplies once per layer stack
    (attention projections and MLP), excluding embedding and head."""
    d, h, kv, hd, f = (m["hidden_size"], m["num_attention_heads"],
                       m["num_key_value_heads"], m["head_dim"],
                       m["intermediate_size"])
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    return m["num_hidden_layers"] * per_layer


def attention_flops(m: dict, context: int) -> int:
    """Scores and weighted values of one query token over ``context``
    keys, all layers."""
    return 4 * m["num_hidden_layers"] * m["num_attention_heads"] * \
        m["head_dim"] * context


def head_flops(m: dict) -> int:
    """Logits of one position over the real vocabulary."""
    return 2 * m["hidden_size"] * m["vocab_size"]


def prefill_flops(m: dict, prompt: int) -> int:
    """A prompt of ``prompt`` tokens: every token through the stack with
    causal attention, logits at the last position only."""
    dense = 2 * matmul_params(m) * prompt
    attn = sum(attention_flops(m, i + 1) for i in range(prompt))
    return dense + attn + head_flops(m)


def decode_flops(m: dict, context: int) -> int:
    """One decode step of one sequence that attends over ``context``
    positions (itself included)."""
    return 2 * matmul_params(m) + attention_flops(m, context) + head_flops(m)


def generate_flops(m: dict, prompt: int, new_tokens: int) -> int:
    """A whole request: prefill yields the first token, then
    ``new_tokens - 1`` decode steps at contexts prompt+1 .. prompt+new-1."""
    return prefill_flops(m, prompt) + sum(
        decode_flops(m, prompt + 1 + i) for i in range(new_tokens - 1))


def decode_attention_work(m: dict, context: int,
                          kv_bytes: int = 2) -> tuple[int, int]:
    """(operations, bytes) of the decode-attention kernel for one sequence
    at one layer: read K and V of ``context`` positions of the model's KV
    heads, read the query and write the output of every query head."""
    h, kv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    ops = 4 * h * hd * context
    nbytes = 2 * context * kv * hd * kv_bytes + 2 * h * hd * kv_bytes
    return ops, nbytes
