"""The one general generator of training traffic: token batches from a seed.

A traffic mix is the data in `workloads/<cell>.json` (`batch`, `seq_len`);
the vocabulary comes from the configuration. Batch number `position` of a
run is a pure function of (seed key, position): every row differs, and the
reference makes the same batch again from the same two numbers.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A key from any whole number the driver may pass (beyond 2**31 too)."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF
    )


def token_batch(key, position, *, batch: int, seq_len: int, vocab_size: int):
    tokens = jax.random.randint(
        jax.random.fold_in(jax.random.fold_in(key, 0x7AFF1C), position),
        (batch, seq_len + 1), 0, vocab_size, dtype=jnp.int32,
    )
    return {"tokens": tokens[:, :-1], "labels": tokens[:, 1:]}


class TokenFeed:
    """The iterable handed to `fit()`. One live position across calls, as a
    resumed job's pipeline has; a span round every `next()` (host clock,
    and a `bench:input` annotation for the device trace)."""

    def __init__(self, key, *, batch, seq_len, vocab_size, sharding):
        self.key = key
        self.position = 0
        self.spans: list[tuple[float, float]] = []
        self.first_draw: float | None = None  # host clock, armed by the driver
        self._make = jax.jit(
            lambda k, p: token_batch(
                k, p, batch=batch, seq_len=seq_len, vocab_size=vocab_size
            ),
            out_shardings={"tokens": sharding, "labels": sharding},
        )

    def batch_at(self, position: int):
        return self._make(self.key, jnp.int32(position))

    def __iter__(self):
        return self

    def __next__(self):
        t0 = time.perf_counter()
        if self.first_draw is None:
            self.first_draw = t0
        with jax.profiler.TraceAnnotation("bench:input"):
            out = self.batch_at(self.position)
        self.position += 1
        self.spans.append((t0, time.perf_counter()))
        return out
