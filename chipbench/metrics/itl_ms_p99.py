"""99th percentile of every gap between two tokens of one request in the
window (one sample per decode step), in ms."""
from chipbench.stats import percentile


def read(r):
    p = percentile([s for _, s in r.steps], 99)
    return None if p is None else p * 1e3
