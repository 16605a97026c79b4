"""Device time of the prefill program in ``other``: the operations that
no block names, in ms per traced prefill (``blocks.read_ms``)."""
from chipbench import blocks


def read(r):
    return blocks.read_ms(r, "prefill", (blocks.OTHER,))
