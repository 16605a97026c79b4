"""Device time of the decode program in the matmul blocks
(``blocks.MATMUL``: ``attn_proj``, ``ffn``, ``head``), in ms per traced
step (``blocks.read_ms``)."""
from chipbench import blocks


def read(r):
    return blocks.read_ms(r, "decode_step", blocks.MATMUL)
