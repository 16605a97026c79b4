"""Device time of the prefill program in the matmul blocks
(``blocks.MATMUL``: ``attn_proj``, ``ffn``, ``head``), in ms per traced
prefill (``blocks.read_ms``)."""
from chipbench import blocks


def read(r):
    return blocks.read_ms(r, "prefill", blocks.MATMUL)
