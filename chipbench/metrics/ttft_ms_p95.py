"""95th percentile of the time to first token over every request whose
first token reached the host in the window, in ms: from the batch's
start (its prompts leave the host) to its first token on the host."""
from chipbench.stats import percentile


def read(r):
    p = percentile(r.ttft_s, 95)
    return None if p is None else p * 1e3
