"""A whole run at test size on the CPU, past the harness's look for a
chip, with the timed path sound and then broken underneath: ``correct``
must come out true and then false for each fault a serving cell can
have.  (Its cells run on one chip, so no exchange between chips can be
left out.)"""
import json
import pathlib
import time

import jax.numpy as jnp
import pytest

from chipbench import catalog, cell

DATA = pathlib.Path(__file__).resolve().parent / "data"
LIMITS = pathlib.Path(__file__).resolve().parents[1] / "limits"
# each test configuration stands for the cells of the architecture it
# shares; it is held to the tightest of their limits
STANDS_FOR = {"tiny-qwen2": ("qwen2-0.5b.decode", "qwen2-0.5b.prefill"),
              "tiny-llama": ("deepseek-67b-l4.decode",
                             "deepseek-67b-l4.prefill")}


def state_unchanged(server, traffic, vocab):
    """The decode step returns the cache it was given."""
    step = server.step
    server.step = lambda p, c, t, i: (step(p, c, t, i)[0], c)


def half_batch(server, traffic, vocab):
    """Rows of the second half are not computed: they get the first
    half's outputs."""
    half = traffic["batch"] // 2

    def dup(out):
        logits, cache = out
        return jnp.concatenate([logits[:half], logits[:half]]), cache
    step, prefill = server.step, server.prefill
    server.step = lambda p, c, t, i: dup(step(p, c, t, i))
    server.prefill = lambda p, b: dup(prefill(p, b))


def token_altered(server, traffic, vocab):
    """The sampled tokens of one decode step are altered where they are
    produced."""
    sample, n = server.sample, [0]

    def altered(logits):
        n[0] += 1
        tok = sample(logits)
        return (tok + 1) % vocab if n[0] % (traffic["gen"] + 1) == 3 \
            else tok
    server.sample = altered


def run(name, fault, seed):
    c = json.loads((DATA / f"{name}.json").read_text())
    traffic = json.loads((DATA / "tiny-traffic.json").read_text())
    limit = min(json.loads((LIMITS / f"{w}.json").read_text())["token_gap"]
                for w in STANDS_FOR[name])
    one = catalog.Cell(name=name, chips=1, config=c,
                       arch=catalog.architecture(c, DATA / f"{name}.json"),
                       traffic=traffic,
                       limits={"token_gap": limit}, end_to_end=[],
                       per_layer=[])
    hook = None if fault is None else \
        (lambda s: fault(s, traffic, c["vocab_size"]))
    return cell.run_cell(one, seed, 0.5, False, time.monotonic(),
                         require_tpu=False, server_hook=hook)


@pytest.mark.parametrize("name", sorted(STANDS_FOR))
def test_sound_run_is_correct(name):
    out = run(name, None, 2**35 + 3)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", [state_unchanged, half_batch,
                                   token_altered])
@pytest.mark.parametrize("name", sorted(STANDS_FOR))
def test_fault_is_not_correct(name, fault):
    out = run(name, fault, 2**35 + 5)
    assert not out["correct"], out["checks"]
