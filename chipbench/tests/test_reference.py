"""The float32 reference against the program's ``lm.prefill`` and
``lm.decode_step`` run in float32 at a small size, and the weights the
two are given."""
import dataclasses
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import model
from chipbench import weights as W
from chipbench.reference import dense_gqa as R

DATA = pathlib.Path(__file__).resolve().parent / "data"
SEED = 2**40 + 17


def config(name):
    return json.loads((DATA / f"{name}.json").read_text())


def reference_logits(m, seed, tokens):
    """Logits at every position of ``tokens`` [T], layer by layer."""
    key = W.base_key(seed)
    g = jax.tree.map(lambda a: a.astype(jnp.float32), W.make_globals(m, key))
    x = jnp.take(g["embed"], tokens, 0)
    for li in range(m["layers"]):
        w = jax.tree.map(lambda a: a.astype(jnp.float32),
                         W.make_layer(m, key, jnp.uint32(li)))
        x = R.layer(m, w, x)
    return R._logits(m, g, R._head(m, g, None), x)


@pytest.mark.parametrize("name", ["tiny-qwen2", "tiny-llama"])
def test_layers_made_alone_equal_the_stacked_whole(name):
    m = W.dims(config(name))
    key = W.base_key(SEED)
    whole = jax.jit(lambda k: W.make_all(m, k))(key)
    for li in range(m["layers"]):
        alone = jax.jit(lambda k, l: W.make_layer(m, k, l))(key,
                                                           jnp.uint32(li))
        for n, a in alone.items():
            np.testing.assert_array_equal(np.asarray(a),
                                          np.asarray(whole["layers"][n][li]))
    assert W.base_key(SEED).tolist() != W.base_key(17).tolist()


@pytest.mark.parametrize("name", ["tiny-qwen2", "tiny-llama"])
def test_reference_matches_program_prefill_and_decode_in_float32(name):
    from repro.models import lm
    from repro.models.lm import RunOptions
    c = config(name)
    m = W.dims(c)
    cfg = model.program_config(c)
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          model.make_params(cfg, m, SEED))
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    P, G = 24, 6
    toks = np.random.default_rng(0).integers(0, m["vocab"], (2, P + G),
                                             dtype=np.int32)
    opts = RunOptions(chunk_q=8, chunk_kv=8, cache_len=P + G, remat=False)
    logits, cache = lm.prefill(cfg32, params, {"tokens": toks[:, :P]}, opts)
    got = [logits]
    for i in range(G - 1):
        logits, cache = lm.decode_step(cfg32, params, cache,
                                       jnp.asarray(toks[:, P + i]), P + i,
                                       opts)
        got.append(logits)
    got = np.stack([np.asarray(g)[:, :m["vocab"]] for g in got], 1)
    for r in range(2):
        want = np.asarray(reference_logits(m, SEED, toks[r, :P + G - 1]))
        np.testing.assert_allclose(got[r], want[P - 1:], rtol=2e-4,
                                   atol=2e-4 * np.abs(want).max())


def test_gaps_are_zero_for_the_reference_own_tokens():
    m = W.dims(config("tiny-llama"))
    toks = np.random.default_rng(1).integers(0, m["vocab"], (20,),
                                             dtype=np.int32)
    # greedy tokens of the reference itself, teacher-forced
    seq = list(toks)
    for _ in range(5):
        seq.append(int(np.argmax(reference_logits(m, SEED,
                                                  np.array(seq))[-1])))
    seq = np.array(seq, np.int32)
    served = seq[20:][None]
    gaps = R.Reference(m).gaps(SEED, jnp.asarray(seq[None, :-1]),
                               jnp.asarray(served), ("fp8",))
    assert np.max(gaps["program"]) == pytest.approx(0.0, abs=1e-6)
    assert np.all(gaps["fp8"] >= 0)
