"""Graphs built on the device from a seed.

A configuration's ``generator`` block names its generator by ``kind``, which
is found as ``bench/generators/<kind>.py``; it returns undirected edges as
two int32 device arrays. ``build`` turns them into the program's ``Graph``
on the device: symmetrize, drop self-loops, sort by (sender, receiver) with
one two-key ``lax.sort``, drop duplicates, and compact into a fixed number
of slots padded with the dump vertex ``n``, then read the CSR offsets off
the sorted senders. Nothing of the edge list passes through the host.

The slot count (``m_pad``) is fixed by the configuration, not by the seed,
so every seed compiles to the same programs and the compile cache holds
across seeds; the real edge count ``m`` is read back as one scalar.
"""

from __future__ import annotations

from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
from jax import lax


@partial(jax.jit, static_argnames=("n", "m_pad"))
def _sorted_csr(s, r, *, n: int, m_pad: int):
    S = jnp.concatenate([s, r])
    R = jnp.concatenate([r, s])
    loop = S == R
    S = jnp.where(loop, n, S)
    R = jnp.where(loop, n, R)
    S, R = lax.sort((S, R), num_keys=2)
    dup = jnp.concatenate([jnp.zeros((1,), bool),
                           (S[1:] == S[:-1]) & (R[1:] == R[:-1])])
    keep = ~dup & (S < n)
    m = jnp.sum(keep, dtype=jnp.int32)
    # kept entries keep their sorted order: scatter each to its rank, and
    # each dropped one to a slot of its own past the end
    rank = jnp.cumsum(keep, dtype=jnp.int32) - 1
    drop_slot = m_pad + jnp.arange(S.shape[0], dtype=jnp.int32)
    pos = jnp.where(keep, rank, drop_slot)
    fill = jnp.full((m_pad,), n, jnp.int32)
    senders = fill.at[pos].set(S, mode="drop", unique_indices=True)
    receivers = fill.at[pos].set(R, mode="drop", unique_indices=True)
    rows = jnp.searchsorted(senders, jnp.arange(n + 1, dtype=jnp.int32),
                            side="left").astype(jnp.int32)
    indptr = jnp.concatenate([rows, m[None]])
    return senders, receivers, indptr, m


def build(s, r, *, n: int, m_pad: int):
    """The program's ``Graph`` from undirected device edges ``(s, r)``.

    Raises if more distinct directed edges survive than ``m_pad`` holds."""
    from repro.graphs import Graph

    senders, receivers, indptr, m = _sorted_csr(s, r, n=n, m_pad=m_pad)
    m = int(m)
    if m > m_pad:
        raise ValueError(f"{m} directed edges do not fit m_pad={m_pad}")
    # the m real edges lead, then padding: a cheap look at the seam
    if m and not (int(senders[m - 1]) < n and int(indptr[n]) == m):
        raise RuntimeError("the device build left padding among the edges")
    # the CSR column ids are the receivers in sender order: one array
    return Graph(senders=senders, receivers=receivers, indptr=indptr,
                 indices=receivers, n=n, m=m)


def generator(kind: str):
    """The generator module ``bench/generators/<kind>.py``: ``edges(gen,
    key)`` gives undirected device edges and the vertex count, ``m_pad(gen)``
    the directed edge slots, and ``stream(gen, key, count)`` the first
    ``count`` edges in the generator's order, on the host."""
    from bench.harness import load_module

    path = Path(__file__).resolve().parent / "generators" / f"{kind}.py"
    if not path.is_file():
        raise ValueError(f"no generator {kind!r} at {path}")
    return load_module(path)
