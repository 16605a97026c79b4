"""What decides ``correct``: a sample of the requests served in the
window, drawn from the seed, run once through the plain float32
reference over each prompt and its served tokens.

The number compared, ``token_gap``, is the widest gap by which a served
token's reference logit lies below the reference's best logit at that
position, in units of the standard deviation of the reference logits
there.  Greedy serving at bfloat16 reads a small gap where rounding
flips a near tie; a fault, or arithmetic below the configuration's
precision, picks tokens further down.  Its limit is per cell, in
``limits/<workload>.json``, set from readings of the program and of the
lower-precision control (PERF.md).
"""
from __future__ import annotations

import numpy as np


def check_rng(seed: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 64), 2])


def sample(batches, k: int, rng: np.random.Generator) -> list:
    """(batch, row) pairs of ``k`` finished requests.  Rows are spread
    over ``min(k, B)`` equal strata of the batch, so that a fault in part
    of the rows is in the sample; each pick's batch is drawn at random."""
    done = [b for b in batches if b.done]
    if not done:
        raise RuntimeError("no finished batch to check")
    rows = done[0].prompt.shape[0]
    strata = min(k, rows)
    picks = set()
    for j in range(k):
        lo, hi = j % strata * rows // strata, (j % strata + 1) * rows // strata
        picks.add((int(rng.integers(len(done))), int(rng.integers(lo, hi))))
    return [(done[b], r) for b, r in sorted(picks)]


def sequences(picks) -> tuple[np.ndarray, np.ndarray]:
    """Reference inputs: each prompt followed by all but its last served
    token [k, P + n - 1], and the served tokens [k, n]."""
    served = np.stack([b.served()[r] for b, r in picks]).astype(np.int32)
    prompts = np.stack([b.prompt[r] for b, r in picks]).astype(np.int32)
    return np.concatenate([prompts, served[:, :-1]], 1), served


def out_of_vocab(batches, vocab: int) -> int:
    """Requests with a served token outside the vocabulary."""
    bad = 0
    for b in batches:
        s = b.served()
        bad += int(np.sum(np.any((s < 0) | (s >= vocab), axis=1)))
    return bad
