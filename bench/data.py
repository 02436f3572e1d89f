"""Seeded corpora and queries, drawn on the device.

A corpus is made of row blocks of equal size, and block ``b`` is a pure
function of the corpus key and ``b``, drawn by one compiled program
(``block``).  Set-up draws every block into one device buffer; the
reference, after the window, draws the same blocks one at a time with
the same program, so it sees the same bits and never holds a full
float32 copy beside anything else.

Two kinds of data, named by a configuration's ``data.kind``:

* ``product``: the narrow-band product embeddings of the paper's
  PRODUCT60M (arXiv:2110.08919 Fig. 1): values in (-0.125, 0.125), half
  of them in the bands +-(0.08, 0.125), the rest a tight centre.
* ``sift_mixture``: SIFT-like descriptors on the uint8 grid, drawn as a
  Gaussian mixture whose component weights follow a Zipf law: each row
  picks a component by its weight, adds Gaussian noise to that
  component's centre, and is floored and clipped to [0, 218].  Centres
  are drawn on the SIFT value profile (gamma(2) x 18).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

#: the largest row block a corpus is drawn in
MAX_BLOCK = 65536
#: the smallest block accepted for a corpus larger than it
MIN_BLOCK = 4096


def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number (more than 32 bits)."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed % (1 << 31)),
                              (seed >> 31) % (1 << 31))


def block_rows(n: int) -> int:
    """The largest divisor of ``n`` that is at most MAX_BLOCK."""
    if n <= MAX_BLOCK:
        return n
    for b in range(MAX_BLOCK, MIN_BLOCK - 1, -1):
        if n % b == 0:
            return b
    raise ValueError(f"n={n} has no divisor in [{MIN_BLOCK}, {MAX_BLOCK}]; "
                     "pick a row count with one")


@dataclasses.dataclass(frozen=True)
class Generator:
    """How rows of one configuration's data are drawn (hashable, so it
    can be a static argument of a jitted call)."""

    kind: str
    d: int
    components: int = 0
    zipf_s: float = 1.0
    noise_sd: float = 0.0

    def consts(self, key: jax.Array) -> tuple:
        """Arrays every block shares: the mixture's centres and the
        cumulative component weights (empty for ``product``)."""
        if self.kind == "product":
            return ()
        w = 1.0 / np.arange(1, self.components + 1) ** self.zipf_s
        cdf = jnp.asarray(np.cumsum(w / w.sum()), jnp.float32)
        centres = _sift_grid(jax.random.gamma(
            jax.random.fold_in(key, 1 << 30), 2.0, (self.components, self.d))
            * 18.0)
        return centres, cdf

    def rows(self, key: jax.Array, b, count: int, consts: tuple) -> jax.Array:
        """Block ``b`` of ``count`` rows, [count, d] float32."""
        kk = jax.random.fold_in(key, b)
        if self.kind == "product":
            return _product_rows(kk, count, self.d)
        if self.kind == "sift_mixture":
            centres, cdf = consts
            kc, kn = jax.random.split(kk)
            comp = jnp.searchsorted(cdf, jax.random.uniform(kc, (count,)))
            comp = jnp.minimum(comp, self.components - 1)
            noise = jax.random.normal(kn, (count, self.d)) * self.noise_sd
            return _sift_grid(centres[comp] + noise)
        raise ValueError(f"unknown data kind {self.kind!r}")


def generator(cfg: dict) -> Generator:
    """The Generator a configuration's ``data`` block describes."""
    data = cfg["data"]
    return Generator(kind=data["kind"], d=int(cfg["d"]),
                     components=int(data.get("components", 0)),
                     zipf_s=float(data.get("zipf_s", 1.0)),
                     noise_sd=float(data.get("noise_sd", 0.0)))


def _sift_grid(x: jax.Array) -> jax.Array:
    return jnp.floor(jnp.clip(x, 0.0, 218.0))


def _product_rows(kk, rows, d):
    # the paper's Fig. 1 profile: half the values in +-(0.08, 0.125),
    # the rest a tight centre, all inside the band
    ka, kb, kc = jax.random.split(kk, 3)
    centre = jax.random.normal(ka, (rows, d)) * 0.04
    band_sign = jnp.sign(jax.random.normal(kb, (rows, d)))
    band = band_sign * jax.random.uniform(kc, (rows, d), minval=0.08,
                                          maxval=0.125)
    pick = jax.random.uniform(kk, (rows, d)) < 0.5
    return jnp.clip(jnp.where(pick, band, centre), -0.12499, 0.12499)


@partial(jax.jit, static_argnums=(0, 3))
def block(gen: Generator, key: jax.Array, b, count: int, consts: tuple):
    """Row block ``b`` (a traced index, so one program serves every
    block): the only way set-up and the reference draw rows, so the two
    see the same bits."""
    return gen.rows(key, b, count, consts)


@partial(jax.jit, donate_argnums=0)
def _put(out, rows, start):
    return jax.lax.dynamic_update_slice_in_dim(out, rows, start, 0)


def corpus(gen: Generator, key: jax.Array, n: int, consts: tuple) -> jax.Array:
    """The whole [n, d] float32 corpus, drawn block by block on the device
    into one buffer."""
    size = block_rows(n)
    out = jnp.zeros((n, gen.d), jnp.float32)
    for b in range(n // size):
        out = _put(out, block(gen, key, b, size, consts), b * size)
    return out


@partial(jax.jit, static_argnums=(0, 2))
def queries(gen: Generator, key: jax.Array, count: int, consts: tuple):
    """[count, d] float32 queries from the same distribution as the rows."""
    return gen.rows(key, 0, count, consts)
