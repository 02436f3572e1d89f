"""Row-blocked fills: build a large array one block of rows at a time, so
a multi-million-row corpus never holds more than one block of
temporaries."""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp


def fill_row_blocks(
    out: jax.Array, block: int, fn: Callable[[jax.Array, jax.Array], jax.Array]
) -> jax.Array:
    """``out`` with rows ``[s, s + block)`` set to ``fn(b, s)`` for each
    block ``b`` (``block`` <= rows).  The last block ends at the last row,
    overlapping its predecessor instead of reading or writing past it."""
    n = out.shape[0]

    def body(b, out):
        start = jnp.minimum(b * block, n - block)
        return jax.lax.dynamic_update_slice_in_dim(out, fn(b, start), start, 0)

    return jax.lax.fori_loop(0, -(-n // block), body, out)
