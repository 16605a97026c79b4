"""Closed-loop serving of batches: the host loop of
``repro.launch.serve.run``, copied so that a window can drive it over
many batches.

Per batch: the prompts go to the device, the compiled prefill runs, then
``gen`` compiled decode steps; after the prefill and after each step the
host takes the greedy token (``argmax`` over the vocabulary) and syncs
it, as ``serve.run`` does.  Each phase runs inside a
``jax.profiler.TraceAnnotation`` (``new_batch``, ``prefill``,
``decode_step``, ``sample_sync``) so that a device trace can say what the
host was doing in each idle gap.  A change to ``serve.run``'s loop is
not measured here until the program exposes a serving entry the
benchmark can drive.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

annotate = jax.profiler.TraceAnnotation


@dataclass
class Server:
    """What one batch needs: the compiled entry points and their
    arguments.  ``prefill``, ``step`` and ``sample`` are the timed path;
    tests wrap them to plant faults."""
    params: dict
    prefill: Callable
    step: Callable
    sample: Callable
    prompt_len: int
    gen: int


@dataclass
class Batch:
    """One batch of requests in flight; ``times[j]`` is when token ``j``
    of every row reached the host."""
    server: Server
    prompt: np.ndarray
    t0: float = 0.0
    times: list = field(default_factory=list)
    tokens: list = field(default_factory=list)
    steps: int = 0
    cache: object = None
    tok: object = None

    def start(self) -> None:
        s = self.server
        self.t0 = time.monotonic()
        with annotate("new_batch"):
            prompt = jnp.asarray(self.prompt)
        with annotate("prefill"):
            logits, self.cache = jax.block_until_ready(
                s.prefill(s.params, {"tokens": prompt}))
        self._take(logits)

    def step(self) -> None:
        s = self.server
        with annotate("decode_step"):
            logits, self.cache = s.step(s.params, self.cache, self.tok,
                                        jnp.int32(s.prompt_len + self.steps))
            logits = jax.block_until_ready(logits)
        self.steps += 1
        self._take(logits)
        if self.done:
            self.cache = self.tok = None

    def _take(self, logits) -> None:
        with annotate("sample_sync"):
            self.tok = self.server.sample(logits)
            self.tokens.append(np.asarray(self.tok))
        self.times.append(time.monotonic())

    @property
    def done(self) -> bool:
        return self.steps == self.server.gen

    def served(self) -> np.ndarray:
        """[B, tokens so far]."""
        return np.stack(self.tokens, 1)


def serve(server: Server, prompts, until: float, batch: Batch | None = None,
          log: list | None = None) -> Batch | None:
    """Serve batches back to back until the first token after ``until``
    (monotonic seconds); ``prompts()`` gives each new batch's prompts.
    Continues ``batch`` if it is still in flight, appends every batch it
    starts to ``log``, and returns the batch in flight at the close."""
    while True:
        if batch is None or batch.done:
            if time.monotonic() >= until:
                return batch
            batch = Batch(server, prompts())
            if log is not None:
                log.append(batch)
            batch.start()
        else:
            batch.step()
        if time.monotonic() >= until:
            return batch
