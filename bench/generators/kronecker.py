"""Graph500 Kronecker generator (specification section 3, the reference
``kronecker_generator``), on the device.

A configuration's ``generator`` block names it by ``kind: kronecker`` and
gives ``scale``, ``edgefactor``, ``a``, ``b`` and ``c``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


@partial(jax.jit, static_argnames=("m", "scale", "a", "b", "c"))
def kronecker_edges(key, *, m: int, scale: int, a: float, b: float,
                    c: float):
    """``m`` edges (Graph500 draws ``edgefactor << scale``), one quadrant
    draw per level with two uniforms, then a random relabelling of the
    ``2**scale`` vertices. The edges are independent draws, so their order
    is already a random shuffle, and the first ``k`` of them are a prefix of
    that shuffle."""
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    k_levels, k_perm = jax.random.split(key)

    def level(i, sr):
        s, r = sr
        k_i, k_j = jax.random.split(jax.random.fold_in(k_levels, i))
        ii = jax.random.uniform(k_i, (m,)) > ab
        jj = jax.random.uniform(k_j, (m,)) > jnp.where(ii, c_norm, a_norm)
        return (s | (ii.astype(jnp.int32) << i),
                r | (jj.astype(jnp.int32) << i))

    zeros = jnp.zeros((m,), jnp.int32)
    s, r = lax.fori_loop(0, scale, level, (zeros, zeros))
    perm = jax.random.permutation(k_perm, 1 << scale).astype(jnp.int32)
    return perm[s], perm[r]


def _draw(gen: dict, key, m: int):
    return kronecker_edges(key, m=m, scale=gen["scale"], a=gen["a"],
                           b=gen["b"], c=gen["c"])


def edges(gen: dict, key):
    """Every generated edge, undirected, on the device, and the vertex
    count."""
    s, r = _draw(gen, key, gen["edgefactor"] << gen["scale"])
    return s, r, 1 << gen["scale"]


def m_pad(gen: dict) -> int:
    """Directed edge slots: every generated edge in both directions, which
    bounds what survives the self-loop drop and the dedup."""
    return 2 * (gen["edgefactor"] << gen["scale"])


def stream(gen: dict, key, count: int):
    """The first ``count`` edges of the generator's random order, self-loops
    dropped, as host arrays, and the vertex count."""
    spare = count + count // 64 + 1024
    s, r = jax.device_get(_draw(gen, key, spare))
    s, r = np.asarray(s), np.asarray(r)
    ok = s != r
    s, r = s[ok][:count], r[ok][:count]
    if s.shape[0] < count:
        raise ValueError("the generator gave too many self-loops")
    return s, r, 1 << gen["scale"]
