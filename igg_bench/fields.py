"""Seeded initial fields, as functions of the global cell index.

A field value depends only on the global (de-duplicated) index of its cell,
so the copies of a cell that neighbouring blocks hold in their overlap agree,
and the program's block layout and the reference's global array are filled
from one formula.  Every seed gives the same sizes and work; only the values
change.  Nothing here imports the program.
"""

from __future__ import annotations

import numpy as np

#: parameters of one bump: centre (3), width (1), amplitude (1)
_BUMP = 5


def draw_bumps(rng: np.random.Generator, n: int, extents, amp) -> np.ndarray:
    """``n`` Gaussian bumps with centres anywhere in the grid, widths of a
    sixteenth to a sixth of the smallest extent, amplitudes in ``amp``."""
    lo = min(extents)
    out = np.empty((n, _BUMP), np.float32)
    for b in range(n):
        out[b, :3] = [rng.uniform(0, e) for e in extents]
        out[b, 3] = rng.uniform(lo / 16, lo / 6)
        out[b, 4] = rng.uniform(*amp)
    return out


def bumps(g, extents, periodic, params):
    """Sum of Gaussian bumps over global index arrays ``g`` (one per dim,
    broadcastable); distances wrap along periodic dims."""
    import jax.numpy as jnp

    total = 0.0
    for b in range(params.shape[0]):
        r2 = 0.0
        for d in range(3):
            dist = g[d].astype(jnp.float32) - params[b, d]
            if periodic[d]:
                dist = dist - extents[d] * jnp.round(dist / extents[d])
            r2 = r2 + (dist / params[b, 3]) ** 2
        total = total + params[b, 4] * jnp.exp(-r2)
    return total


def hash_uniform(g, salt):
    """Uniform [0, 1) noise from the global index and a 32-bit salt
    (an integer hash; no state, so any block can evaluate its own cells)."""
    import jax.numpy as jnp

    u = jnp.uint32
    h = (g[0].astype(u) * u(0x9E3779B1)) ^ (g[1].astype(u) * u(0x85EBCA77))
    h = h ^ (g[2].astype(u) * u(0xC2B2AE3D)) ^ salt.astype(u)
    h = h ^ (h >> 16)
    h = h * u(0x7FEB352D)
    h = h ^ (h >> 15)
    h = h * u(0x846CA68B)
    h = h ^ (h >> 16)
    return (h >> 8).astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))


def global_index(shape, dims, overlaps, periodic, local_shape):
    """Global de-duplicated index arrays and extents of a block-layout field.

    ``shape`` is the field's block-layout shape (``dims * s`` per dim, ``s``
    its local size); a staggered field (``s = n + 1``) carries one more cell
    of overlap.  Local cell ``l`` of block ``c`` is global cell
    ``(c * (s - o) + l) mod N``, the library's ownership rule for
    ``gather(dedup=True)``."""
    import jax.numpy as jnp
    from jax import lax

    g, ext = [], []
    for d in range(3):
        s = shape[d] // dims[d]
        o = overlaps[d] + (s - local_shape[d])
        n_glob = dims[d] * (s - o) + (0 if periodic[d] else o)
        i = lax.broadcasted_iota(jnp.int32, shape, d)
        g.append(((i // s) * (s - o) + i % s) % n_glob)
        ext.append(n_glob)
    return g, ext


def layout_maps(shape, dims, overlaps, periodic, local_shape):
    """Per dim, two index vectors between a block-layout field and its global
    de-duplicated array, by the ownership rule of `global_index`:
    ``to_global[d]`` holds, for each global cell, a block-layout position
    that holds it (the later block's, for a cell two blocks share); and
    ``to_block[d]`` the global cell of each block-layout position."""
    to_global, to_block = [], []
    for d in range(3):
        s = shape[d] // dims[d]
        o = overlaps[d] + (s - local_shape[d])
        n_glob = dims[d] * (s - o) + (0 if periodic[d] else o)
        i = np.arange(n_glob)
        c = np.minimum(i // (s - o), dims[d] - 1)
        to_global.append(c * s + i - c * (s - o))
        j = np.arange(shape[d])
        to_block.append(((j // s) * (s - o) + j % s) % n_glob)
    return to_global, to_block
