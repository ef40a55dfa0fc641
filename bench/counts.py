"""Operations and bytes the model needs, computed from its sizes.

Model FLOPs count multiply-adds as two operations and only what the
algorithm requires: the matrix products of every processed token, the
attention of each token over its own context (``position + 1`` keys),
and the vocabulary projection for tokens whose next token is chosen.
Padding rows and padded queries are not work.  Minimum bytes are each
weight read once per step plus each row's own cached keys and values
read once and its new ones written once.
"""
from __future__ import annotations

BF16 = 2


def layer_matmul_params(dims: dict) -> int:
    """Weights in one layer's matrix products (norm gains left out)."""
    d, f = dims["d_model"], dims["d_ff"]
    qw = dims["n_heads"] * dims["head_dim"]
    kw = dims["n_kv_heads"] * dims["head_dim"]
    return d * qw + 2 * d * kw + qw * d + 3 * d * f


def head_params(dims: dict) -> int:
    return dims["d_model"] * dims["vocab"]


def weight_params(dims: dict) -> int:
    """Every weight held: layers, embedding, and an untied head."""
    n = dims["layers"] * (layer_matmul_params(dims) + 2 * dims["d_model"]
                          + 2 * dims["head_dim"]) + dims["d_model"]
    n += dims["vocab"] * dims["d_model"]
    if not dims["tied"]:
        n += head_params(dims)
    return n


def kv_bytes_per_token(dims: dict, dtype_bytes: int = BF16) -> int:
    return dims["layers"] * 2 * dims["n_kv_heads"] * dims["head_dim"] \
        * dtype_bytes


def token_flops(dims: dict, position: int, head: bool) -> float:
    """FLOPs of one token at ``position`` (0-based) through the stack,
    plus the vocabulary projection when ``head``."""
    mm = 2.0 * dims["layers"] * layer_matmul_params(dims)
    attn = 4.0 * dims["layers"] * dims["n_heads"] * dims["head_dim"] \
        * (position + 1)
    return mm + attn + (2.0 * head_params(dims) if head else 0.0)


def span_flops(dims: dict, start: int, stop: int, head_last: bool) -> float:
    """FLOPs of the tokens at positions ``start .. stop - 1`` (one chunk
    or one whole prompt), with the head on the last one if asked."""
    n = stop - start
    if n <= 0:
        return 0.0
    mm = 2.0 * dims["layers"] * layer_matmul_params(dims) * n
    # sum over p of (p + 1) for p in [start, stop)
    keys = (start + 1 + stop) * n / 2.0
    attn = 4.0 * dims["layers"] * dims["n_heads"] * dims["head_dim"] * keys
    return mm + attn + (2.0 * head_params(dims) if head_last else 0.0)


def step_min_bytes(dims: dict, contexts, new_tokens: int,
                   dtype_bytes: int = BF16) -> float:
    """Least bytes one step moves: every weight once, each row's cached
    context once, and the keys and values of ``new_tokens`` written."""
    kv = kv_bytes_per_token(dims, dtype_bytes)
    return (weight_params(dims) * dtype_bytes + kv * sum(contexts)
            + kv * new_tokens)
