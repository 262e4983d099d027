"""Operations and bytes a GQA decoder needs (grouped-query attention, a
gated MLP, one output head), from shapes and lengths.

Kept with the benchmark so that no PR that claims a gain can change how
the work is counted. A family whose block has this shape points its work
counts here (README, "A family"); ``sizes`` is that family's ``Sizes``
(the published sizes from the configuration's file: ``hidden``, ``inter``,
``layers``, ``heads``, ``kv_heads``, ``head_dim``, ``vocab``). A block of
another shape brings its own counts in its family's file.
"""

from __future__ import annotations


def layer_matmul_params(sizes) -> int:
    attn = sizes.hidden * sizes.head_dim * (2 * sizes.heads + 2 * sizes.kv_heads)
    return attn + 3 * sizes.hidden * sizes.inter


def attention_flops(sizes, context: int) -> int:
    """QK^T and PV of one query token over ``context`` keys, all layers:
    2 matmuls x 2 flops x heads x head_dim x context."""
    return 4 * sizes.heads * sizes.head_dim * context * sizes.layers


def prompt_flops(sizes, prompt_tokens: int) -> int:
    """A prompt's forward pass: every token through every layer's
    matmuls, causal attention (token p sees p + 1 keys), and the output
    head once, for the last token."""
    body = 2 * layer_matmul_params(sizes) * sizes.layers * prompt_tokens
    attn = attention_flops(sizes, 1) * prompt_tokens * (prompt_tokens + 1) // 2
    return body + attn + 2 * sizes.hidden * sizes.vocab


def output_token_flops(sizes, context: int) -> int:
    """One decoded token whose query sees ``context`` keys."""
    body = 2 * (layer_matmul_params(sizes) * sizes.layers + sizes.hidden * sizes.vocab)
    return body + attention_flops(sizes, context)


def decode_attention(sizes, keys: int, queries: int, kv_bytes: int = 2):
    """Decode attention over ``queries`` one-token queries that see
    ``keys`` cached keys between them, all layers: (flops, bytes). The
    bytes are the K and V rows that hold those keys, once each, plus the
    queries and the outputs; an ideal kernel moves nothing else."""
    flops = 4 * sizes.heads * sizes.head_dim * keys * sizes.layers
    kv = 2 * sizes.kv_heads * sizes.head_dim * kv_bytes * keys
    q_and_out = 2 * queries * sizes.heads * sizes.head_dim * 2
    return flops, (kv + q_and_out) * sizes.layers
