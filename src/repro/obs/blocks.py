"""The blocks of the served step programs, and which block each of a
compiled program's operations belongs to.

The model code runs each block under ``jax.named_scope(<block>)``:

- ``attn_proj``: the q/k/v projections with their biases and rope, and
  the output projection (``attention.qkv_project``, ``out_project``);
- ``attn_core``: the attention itself (``attention.sdpa``) and the
  cache writes (decode's two ``dynamic_update_slice``s, prefill's
  ``lm._to_cache_buf``);
- ``ffn``: ``ffn.dense_ffn`` and ``ffn.moe_ffn``;
- ``head``: the final norm and ``lm.compute_logits``;

and a state-space layer's (``SSM_BLOCKS``; ``models/ssm.py``):

- ``ssm_proj``: its input and output projections;
- ``ssm_core``: the causal convolution, the chunked scan or the decode
  step's state update, the D skip and the grouped gated norm.

``BLOCKS`` are the blocks every served transformer step program names;
a program of a state-space model names ``SSM_BLOCKS`` besides.  A
hybrid layer's per-layer adapter and linear are under ``ffn``.

Everything else (embedding, the layer loop's slicing of stacked weights
and caches, norms and residual adds that XLA does not fuse into a block,
copies XLA makes) is left unnamed: the remainder.

The scope lands in the ``op_name`` metadata of every HLO instruction
made from the block, fused ones included.  A device trace names an
operation only by its instruction name (``fusion.82``), so
``op_blocks`` reads the compiled program's text once and maps each
instruction to its block.
"""
from __future__ import annotations

import re

BLOCKS = ("attn_proj", "attn_core", "ffn", "head")
ATTN_PROJ, ATTN_CORE, FFN, HEAD = BLOCKS
SSM_BLOCKS = ("ssm_proj", "ssm_core")
SSM_PROJ, SSM_CORE = SSM_BLOCKS
_NAMED = BLOCKS + SSM_BLOCKS

# ``  %fusion.12 = bf16[8]{0} fusion(...), ..., metadata={op_name="..."``
_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=]+)\s+=\s.*?'
                    r'\bmetadata=\{[^}]*?\bop_name="([^"]*)"', re.M)


def op_names(hlo_text: str) -> dict[str, str]:
    """HLO instruction name -> its ``op_name`` metadata, for every
    instruction of ``hlo_text`` that carries one."""
    return dict(_INSTR.findall(hlo_text))


def block_of(op_name: str) -> str | None:
    """The innermost of ``BLOCKS`` and ``SSM_BLOCKS`` on the scope path
    ``op_name``."""
    for part in reversed(op_name.split("/")):
        if part in _NAMED:
            return part
    return None


def op_blocks(compiled) -> dict[str, str]:
    """HLO instruction name -> block, for a compiled executable
    (``jax.stages.Compiled``); instructions outside every block are
    absent."""
    out = {}
    for name, op_name in op_names(compiled.as_text()).items():
        block = block_of(op_name)
        if block is not None:
            out[name] = block
    return out
