"""Operations and bytes that serving a dense GQA decoder needs, from
the published sizes (``weights.dims``): the yardstick of the
``*_mfu`` metrics.

Counted: every matmul of every token (2 operations per multiply-add),
causal attention as each query against the keys at or before it, and
the head only where a token is sampled.  Not counted: norms, rotary
embeddings, softmax, activations, masking and any work a program does
beyond this (attention over masked or empty slots).  So a program's
time can never be shorter than these counts at the chip's peaks.
"""
from __future__ import annotations


def layer_matmul_params(m: dict) -> int:
    d, f = m["d"], m["f"]
    q, kv = m["heads"] * m["head_dim"], m["kv_heads"] * m["head_dim"]
    return d * q + 2 * d * kv + q * d + 3 * d * f


def _attn_flops(m: dict, keys: int) -> int:
    """One query against ``keys`` keys in every layer: q.k and p.v."""
    return m["layers"] * 4 * m["heads"] * m["head_dim"] * keys


def _head_flops(m: dict) -> int:
    return 2 * m["d"] * m["vocab"]


def prefill_flops(m: dict, batch: int, prompt: int) -> int:
    """A prompt of ``prompt`` tokens per row; logits at the last one."""
    per_row = (prompt * m["layers"] * 2 * layer_matmul_params(m)
               + _attn_flops(m, prompt * (prompt + 1) // 2)
               + _head_flops(m))
    return batch * per_row


def decode_flops(m: dict, batch: int, pos: int) -> int:
    """One decode step writing position ``pos`` (it attends to pos + 1
    keys) for ``batch`` rows."""
    return batch * (m["layers"] * 2 * layer_matmul_params(m)
                    + _attn_flops(m, pos + 1) + _head_flops(m))


def kv_bytes_per_token(m: dict) -> int:
    """bf16 keys and values of one token in every layer."""
    return m["layers"] * 2 * m["kv_heads"] * m["head_dim"] * 2


def weight_read_bytes(m: dict, batch: int) -> int:
    """Weights one step reads: every layer once (bf16 matrices and
    biases, f32 gains), the head once, and the embedding rows of the
    batch where the head is not the embedding."""
    d, n = m["d"], m["layers"]
    q, kv = m["heads"] * m["head_dim"], m["kv_heads"] * m["head_dim"]
    layer = 2 * layer_matmul_params(m) + 4 * 2 * d
    if m["qkv_bias"]:
        layer += 2 * (q + 2 * kv)
    head = 2 * m["vocab"] * d + 4 * d
    rows = 0 if m["tied"] else 2 * batch * d
    return n * layer + head + rows


def decode_bytes(m: dict, batch: int, pos: int) -> int:
    """One decode step at position ``pos``: the weights once, the keys
    and values of the ``pos`` filled positions, the new token's keys and
    values, and the float32 logits."""
    kv = kv_bytes_per_token(m)
    return (weight_read_bytes(m, batch) + batch * pos * kv + batch * kv
            + batch * m["vocab"] * 4)


def decode_roofline_s(m: dict, peaks: dict, batch: int, pos: int) -> float:
    """The least time one decode step can take on a chip with ``peaks``."""
    return max(decode_flops(m, batch, pos) / peaks["bf16_flops"],
               decode_bytes(m, batch, pos) / peaks["hbm_bytes_per_s"])
