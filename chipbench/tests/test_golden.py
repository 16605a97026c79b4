"""The dense GQA architecture's module makes, at the test sizes, exactly
what the harness made before its dense parts moved there from
``weights``, ``model`` and ``counts``: the numbers in
``data/golden-dense_gqa.json`` were recorded from the harness before the
move.  The program's parameters by a SHA-256 of every leaf, the counts
of the ``*_mfu`` metrics, and the reference's gaps of a fixed sequence
with both lower-precision controls, all compared exactly."""
import hashlib
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import catalog

DATA = pathlib.Path(__file__).resolve().parent / "data"
GOLDEN = json.loads((DATA / "golden-dense_gqa.json").read_text())
SEED = 2**40 + 17
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def arch(name):
    path = DATA / f"{name}.json"
    c = json.loads(path.read_text())
    a = catalog.architecture(c, path)
    return c, a, a.dims(c)


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_parameters_are_what_they_were(name):
    c, a, m = arch(name)
    assert m == GOLDEN[name]["dims"]
    params = a.make_params(a.program_config(c), m, SEED)
    got = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        x = np.asarray(leaf)
        got[jax.tree_util.keystr(path)] = [
            list(x.shape), str(x.dtype),
            hashlib.sha256(x.tobytes()).hexdigest()]
    assert got == GOLDEN[name]["leaves"]


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_counts_are_what_they_were(name):
    _, a, m = arch(name)
    g = GOLDEN[name]
    for bp, want in g["prefill_flops"].items():
        assert a.prefill_flops(m, *map(int, bp.split(","))) == want
    for bp, want in g["decode_roofline_s"].items():
        assert a.decode_roofline_s(m, PEAKS, *map(int, bp.split(","))) \
            == want


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_reference_gaps_are_what_they_were(name):
    _, a, m = arch(name)
    rng = np.random.default_rng(3)
    seqs = rng.integers(0, m["vocab"], (2, 24), dtype=np.int32)
    served = rng.integers(0, m["vocab"], (2, 6), dtype=np.int32)
    gaps = a.Reference(m).gaps(SEED, jnp.asarray(seqs), jnp.asarray(served),
                               ("fp8", "int8"))
    want = GOLDEN[name]["gaps"]
    assert set(gaps) == set(want)
    for k, v in gaps.items():
        np.testing.assert_array_equal(np.asarray(v, np.float32),
                                      np.asarray(want[k], np.float32))
