"""Synthetic dataset generators matching the paper's evaluation corpora.

The paper evaluates on three families:
  * PRODUCT60M — product embeddings whose values cluster in a very narrow
    band (Fig 1: values exclusively in (-.125, .125), 50% within
    +-(.08, .125)).  ``product_embeddings`` reproduces that distribution:
    a heavy-centre Gaussian mixture clipped to the band, constant across
    dimensions (the paper's §4.1 interdimensional-uniformity regime).
  * SIFT — 128-dim local image descriptors, non-negative, heavy-tailed,
    L2 metric.  ``sift_like`` mimics the value profile (gamma-distributed
    magnitudes, integer-ish grid) at configurable scale.
  * Glove100 — 100-dim word embeddings, roughly Gaussian per dim with
    per-dimension spread, angular metric.  ``glove_like``.

All generators return (corpus [N, d] f32, queries [Q, d] f32), drawn on
the device in seeded row blocks (``_draw_rows``) so that a corpus of
millions of rows never holds more than one block of temporaries.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.blocks import fill_row_blocks

#: rows per seeded draw: bounds each draw's temporaries (a few [rows, d]
#: f32 arrays) while the output is filled block by block
BLOCK_ROWS = 65536


@partial(jax.jit, static_argnums=(0, 2, 3))
def _draw_blocks(draw, key, rows: int, d: int, *consts):
    """[rows, d] filled by ``draw(fold_in(key, b), BLOCK_ROWS, d, *consts)``
    per block b; the last block is drawn whole and ends at row ``rows``."""
    return fill_row_blocks(
        jnp.zeros((rows, d), jnp.float32), BLOCK_ROWS,
        lambda b, _start: draw(jax.random.fold_in(key, b), BLOCK_ROWS, d,
                               *consts))


def _draw_rows(draw, key, rows: int, d: int, *consts) -> jax.Array:
    """``draw(key, rows, d, *consts)``, in seeded blocks past BLOCK_ROWS
    rows (same distribution; a set that fits one block is one draw)."""
    if rows <= BLOCK_ROWS:
        return draw(key, rows, d, *consts)
    return _draw_blocks(draw, key, rows, d, *consts)


def _product_rows(kk, rows, d):
    ka, kb, kc = jax.random.split(kk, 3)
    # mixture: 50% in +-(.08, .125) band tails, rest tight at centre
    centre = jax.random.normal(ka, (rows, d)) * 0.04
    band_sign = jnp.sign(jax.random.normal(kb, (rows, d)))
    band = band_sign * jax.random.uniform(kc, (rows, d), minval=0.08, maxval=0.125)
    pick = jax.random.uniform(kk, (rows, d)) < 0.5
    x = jnp.where(pick, band, centre)
    return jnp.clip(x, -0.12499, 0.12499)


def _sift_rows(kk, rows, d):
    mag = jax.random.gamma(kk, 2.0, (rows, d)) * 18.0
    return jnp.floor(jnp.clip(mag, 0.0, 218.0))  # SIFT's uint8-ish grid


def _glove_rows(kk, rows, d, dim_scale):
    return jax.random.normal(kk, (rows, d)) * dim_scale[None, :]


def product_embeddings(
    n: int,
    d: int = 256,
    n_queries: int = 1000,
    key: jax.Array | None = None,
):
    """Narrow-band e-commerce-style embeddings (paper Fig 1)."""
    if key is None:
        key = jax.random.PRNGKey(0)
    k1, k2, _k3, _k4 = jax.random.split(key, 4)
    corpus = _draw_rows(_product_rows, k1, n, d)
    # queries live in the same semantic space (paper: 1000 search queries)
    queries = _draw_rows(_product_rows, k2, n_queries, d)
    return corpus, queries


def sift_like(n: int, d: int = 128, n_queries: int = 1000, key: jax.Array | None = None):
    """SIFT-style descriptors: non-negative, gamma-ish, L2 metric."""
    if key is None:
        key = jax.random.PRNGKey(1)
    k1, k2 = jax.random.split(key)
    return (_draw_rows(_sift_rows, k1, n, d),
            _draw_rows(_sift_rows, k2, n_queries, d))


def glove_like(n: int, d: int = 100, n_queries: int = 1000, key: jax.Array | None = None):
    """GloVe-style word embeddings: per-dim Gaussian, angular metric."""
    if key is None:
        key = jax.random.PRNGKey(2)
    k1, k2, k3 = jax.random.split(key, 3)
    # per-dimension scale spread (glove dims are not iso-scaled)
    dim_scale = 0.3 + jax.random.uniform(k3, (d,)) * 0.5
    return (_draw_rows(_glove_rows, k1, n, d, dim_scale),
            _draw_rows(_glove_rows, k2, n_queries, d, dim_scale))


DATASETS = {
    "product": product_embeddings,
    "sift": sift_like,
    "glove": glove_like,
}

METRIC_FOR = {"product": "ip", "sift": "l2", "glove": "angular"}


def load(name: str, n: int, n_queries: int = 1000, key: jax.Array | None = None,
         d: int | None = None):
    """(corpus, queries, metric) for a named paper dataset family, at the
    family's own width unless ``d`` is given."""
    kw = {} if d is None else {"d": d}
    corpus, queries = DATASETS[name](n, n_queries=n_queries, key=key, **kw)
    return corpus, queries, METRIC_FOR[name]
