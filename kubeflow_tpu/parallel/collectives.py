"""Collective helpers for `shard_map` code.

The reference's collectives lived outside the repo entirely (TF's gRPC
parameter server and Horovod's NCCL ring — SURVEY.md §2.2 "Communication
backends"). Here they are XLA collectives over ICI/DCN, wrapped only thinly:
the wrappers add ring-neighbor index math (the part that is easy to get wrong)
and keep call sites readable. Everything is usable only inside
`jax.shard_map` / `pjit`-traced code.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax


def axis_size(axis: str) -> int:
    """Static size of a named mesh axis, from inside traced code — a
    Python int, so the ring loops unroll at trace time."""
    return lax.axis_size(axis)


def axis_index(axis: str) -> jax.Array:
    return lax.axis_index(axis)


def psum(x: Any, axis: str | tuple[str, ...]) -> Any:
    return lax.psum(x, axis)


def pmean(x: Any, axis: str | tuple[str, ...]) -> Any:
    return lax.pmean(x, axis)


def all_gather(x: Any, axis: str, *, tiled: bool = True, gather_axis: int = 0) -> Any:
    """Gather shards along `axis`; tiled=True concatenates on `gather_axis`."""
    return lax.all_gather(x, axis, axis=gather_axis, tiled=tiled)


def reduce_scatter(x: Any, axis: str, *, scatter_axis: int = 0) -> Any:
    """Sum over `axis` then keep this device's 1/n slice of `scatter_axis`."""
    return lax.psum_scatter(x, axis, scatter_dimension=scatter_axis, tiled=True)


def all_to_all(x: Any, axis: str, *, split_axis: int, concat_axis: int) -> Any:
    """The EP/MoE dispatch primitive (and Ulysses-style sequence exchange)."""
    return lax.all_to_all(
        x, axis, split_axis=split_axis, concat_axis=concat_axis, tiled=True
    )


def ppermute_ring(x: Any, axis: str, *, shift: int = 1) -> Any:
    """Rotate shards around the `axis` ring by `shift` (ring attention's hop).

    perm[i] = (i + shift) % n, i.e. every device sends its shard `shift`
    neighbors "up" the ring; on TPU this lowers to nearest-neighbor ICI
    transfers when `axis` is an innermost mesh axis.
    """
    n = axis_size(axis)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis, perm=perm)


def psum_ring_bidirectional(x: Any, axis: str) -> Any:
    """psum over `axis`; name documents intent at call sites where the ring
    (not tree) algorithm is what XLA will pick on a torus axis."""
    return lax.psum(x, axis)


def unreplicate(tree: Any) -> Any:
    """Host-side: fetch fully-replicated arrays as single host values."""
    return jax.tree_util.tree_map(lambda x: jax.device_get(x), tree)
