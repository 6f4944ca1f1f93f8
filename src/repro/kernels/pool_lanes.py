"""How the paged GQA kernels read the lane-dense page pool.

The pool holds K (and V) as ``(L, n_pages, page, kvh * hd)``: one
token's kv heads side by side on the lane axis, every layer in one
array.  The kernels take the whole pool and the layer as a
scalar-prefetch operand, and read one ``(page, kvh * hd)`` block per
grid step — the pool as it lies, so XLA neither slices a layer out nor
relays it out for them (docs/paged_kv.md).

Mosaic slices lanes only at whole 128-lane tiles, and a head of
``hd`` = 64 lanes starts mid-tile.  So the kernels cut the row into
lane blocks of ``g`` kv heads, ``lcm(hd, 128)`` lanes wide, and each
query head is spread over its block: its values on its own kv head's
lanes, zeros on the others (``spread_heads``).  A spread head's dot
with the block's K row is then exactly its dot with its own kv head's
K, and its P·V over the block holds its output on its kv head's lanes,
which ``gather_heads`` takes out.  At ``hd`` = 64 the block is the one
tile a 64-lane head pads to anyway; at ``hd`` a multiple of 128,
``g`` = 1 and the queries pass as they are.
"""
from __future__ import annotations

import math

import jax.numpy as jnp

LANES = 128


def heads_per_block(kvh: int, hd: int) -> int:
    """kv heads per lane block: enough for a whole number of 128-lane
    tiles, or all of them when the row is not a multiple of that."""
    g = math.lcm(hd, LANES) // hd
    return g if kvh % g == 0 else kvh


def spread_heads(q, kvh: int, g: int):
    """(..., h, hd) -> (..., h, g * hd): query head i of kv head
    ``i // rep`` keeps its values on lanes ``(i // rep) % g`` of its
    lane block and is zero on the block's other heads' lanes."""
    if g == 1:
        return q
    *lead, h, hd = q.shape
    q = q.reshape(*lead, kvh // g, g, h // kvh, 1, hd)
    eye = jnp.eye(g, dtype=q.dtype).reshape(g, 1, g, 1)
    return (q * eye).reshape(*lead, h, g * hd)


def gather_heads(o, kvh: int, g: int):
    """(..., h, g * hd_v) -> (..., h, hd_v): each head's own lanes of
    its P·V over the lane block (the inverse of ``spread_heads``)."""
    if g == 1:
        return o
    *lead, h, w = o.shape
    o = o.reshape(*lead, kvh // g, g, h // kvh, g, w // g)
    o = jnp.diagonal(o, axis1=-4, axis2=-2)    # (..., blk, rep, hd_v, g)
    return jnp.moveaxis(o, -1, -3).reshape(*lead, h, w // g)
