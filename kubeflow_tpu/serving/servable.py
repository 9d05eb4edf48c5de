"""Servable: a loaded model behind a bucketed, jit-compiled predict fn.

TPU-first design notes:

- **Static batch buckets.** XLA compiles one program per input shape; a
  server that forwards raw request batch sizes would recompile on every
  new size (seconds each on TPU). Requests are padded up to the nearest
  bucket (powers of two up to ``max_batch``), so the server compiles at
  most ``log2(max_batch)+1`` programs, all warmed at load time.
- **Device residency.** Params are placed on device once at load; the hot
  path moves only the request batch.
- **Larger requests** are split into ``max_batch`` chunks and re-batched
  through the same buckets — throughput stays on the biggest program.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def _buckets(max_batch: int) -> list[int]:
    out, b = [], 1
    while b < max_batch:
        out.append(b)
        b *= 2
    out.append(max_batch)
    return out


@dataclasses.dataclass
class Servable:
    """One model version the server can execute."""

    name: str
    apply_fn: Callable[[Any, jax.Array], jax.Array]
    variables: Any
    version: int = 1
    max_batch: int = 64
    # Pin execution to a specific device: one chip of several for a
    # replica behind the router, or jax.devices("cpu")[0] to exercise
    # the serving stack with no accelerator in the loop. None = default
    # device.
    device: Any = None

    def __post_init__(self):
        self.variables = (
            jax.device_put(self.variables, self.device)
            if self.device is not None
            else jax.device_put(self.variables)
        )
        self._jitted = jax.jit(self.apply_fn)
        self._bucket_sizes = _buckets(self.max_batch)

    def _to_device(self, batch) -> jax.Array:
        if self.device is not None:
            # Straight host→device placement: jnp.asarray first would
            # land the batch on the DEFAULT device and copy it to the
            # pinned one from there.
            return jax.device_put(batch, self.device)
        return jnp.asarray(batch)

    @classmethod
    def from_module(
        cls,
        name: str,
        module,
        variables: Any,
        *,
        version: int = 1,
        max_batch: int = 64,
        warmup_example=None,
        device=None,
        **apply_kwargs,
    ) -> "Servable":
        """Wrap a flax module (``module.apply``) as a servable. Pass
        ``warmup_example`` (one instance, no batch dim) to compile every
        batch bucket before the servable takes traffic."""

        def apply_fn(variables, batch):
            return module.apply(variables, batch, **apply_kwargs)

        servable = cls(
            name, apply_fn, variables, version=version,
            max_batch=max_batch, device=device,
        )
        if warmup_example is not None:
            servable.warmup_with(warmup_example)
        return servable

    @classmethod
    def from_checkpoint(
        cls,
        name: str,
        module,
        ckpt_dir,
        example_input: jax.Array,
        *,
        max_batch: int = 64,
        **apply_kwargs,
    ) -> "Servable":
        """Restore params from an orbax checkpoint dir written by the
        training loop (`kubeflow_tpu.train.checkpoint`). The abstract state
        comes from a module init on the example input; the servable version
        is the checkpoint step, so clients can see which step is live."""
        from kubeflow_tpu.train.checkpoint import Checkpointer

        variables = jax.eval_shape(
            lambda: module.init(jax.random.PRNGKey(0), example_input)
        )
        # read_only: serving must never rename a training run's steps
        # (e.g. a committed save whose manifest is still in flight).
        ckpt = Checkpointer(ckpt_dir, read_only=True)
        try:
            restored = ckpt.restore_latest(variables)
        finally:
            ckpt.close()
        if restored is None:
            raise FileNotFoundError(f"no checkpoint under {ckpt_dir}")
        variables, step = restored.state, restored.step
        return cls.from_module(
            name, module, variables,
            version=max(step, 1), max_batch=max_batch,
            # The checkpoint path is the serving deployment path, so warm
            # every bucket here — first-compile must not land on a request.
            warmup_example=np.asarray(example_input)[0],
            **apply_kwargs,
        )

    def _bucket_for(self, n: int) -> int:
        for b in self._bucket_sizes:
            if n <= b:
                return b
        return self.max_batch

    def predict(self, instances: Sequence) -> np.ndarray:
        """Run inference on a list of instances (one array-like each).

        Pads to the nearest bucket, executes the jitted program, slices the
        padding back off. Oversized requests are chunked at max_batch.
        """
        batch = np.asarray(instances)
        if batch.shape[0] == 0:
            raise ValueError("empty instances")
        if batch.shape[0] > self.max_batch:
            parts = [
                self.predict(batch[i : i + self.max_batch])
                for i in range(0, batch.shape[0], self.max_batch)
            ]
            return np.concatenate(parts, axis=0)
        n = batch.shape[0]
        bucket = self._bucket_for(n)
        if bucket != n:
            pad = np.zeros((bucket - n, *batch.shape[1:]), batch.dtype)
            batch = np.concatenate([batch, pad], axis=0)
        out = self._jitted(self.variables, self._to_device(batch))
        return np.asarray(out)[:n]

    def warmup_with(self, example_instance) -> None:
        """Compile every bucket before serving traffic (first compile on
        TPU is tens of seconds; it must not land on a user request)."""
        one = np.asarray(example_instance)[None]
        for b in self._bucket_sizes:
            batch = np.repeat(one, b, axis=0)
            self._jitted(
                self.variables, self._to_device(batch)
            ).block_until_ready()
