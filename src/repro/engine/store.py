"""Corpus storage for the scoring engine: ``CodeStore`` and ``PQStore``.

A ``CodeStore`` owns one corpus payload at any precision the paper's Eq. 1
family supports — fp32 vectors, int8 codes, or bit-packed int4 codes
(two per byte, via :mod:`repro.core.pack`) — plus the quantization
constants and a row-id ``base`` so shard-local stores rebase their ids for
the distributed merge.  Every byte the index holds for *vector* data lives
here, so ``memory_bytes()`` is the honest Table-1/2 accounting for every
index kind (the 4-bit arm really is half the int8 arm).

``PQStore`` is the product-quantization counterpart: 1-byte codewords plus
the per-subspace codebooks the ADC scan gathers from.

Stores are frozen dataclass-pytrees: jit/vmap-safe, and their static
fields (n, d, bits, packed, base) ride in the treedef so jitted engine
entry points specialize per storage layout.

Odd dimensions under packing: int4 packing needs an even dim, so the
store pads codes with one zero-code column before packing and
``encode_queries`` appends the matching zero column — code 0 x code 0
contributes 0 to IP and L2 alike, so scores are unchanged.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import pack as PK
from repro.core import quant as Qz


def _params_equal(a: Optional[Qz.QuantParams], b: Optional[Qz.QuantParams]) -> bool:
    """Exact (bit-level) equality of two quantization-constant sets."""
    if a is None or b is None:
        return a is None and b is None
    return (
        a.bits == b.bits
        and a.scheme == b.scheme
        and np.array_equal(np.asarray(a.lo), np.asarray(b.lo))
        and np.array_equal(np.asarray(a.hi), np.asarray(b.hi))
        and np.array_equal(np.asarray(a.zero), np.asarray(b.zero))
    )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CodeStore:
    """One corpus, one precision, one id space."""

    n: int = dataclasses.field(metadata=dict(static=True))
    d: int = dataclasses.field(metadata=dict(static=True))       # logical dim
    bits: int = dataclasses.field(metadata=dict(static=True))    # 32 == fp32
    packed: bool = dataclasses.field(metadata=dict(static=True))
    data: jax.Array           # [N, d] f32 | [N, d_eff] int | [N, d_eff/2] u8
    params: Optional[Qz.QuantParams]
    base: int = dataclasses.field(default=0, metadata=dict(static=True))

    # -- construction ------------------------------------------------------
    @staticmethod
    def dense(vectors: jax.Array, base: int = 0) -> "CodeStore":
        """fp32 storage (the unquantized arm)."""
        vectors = jnp.asarray(vectors, jnp.float32)
        n, d = vectors.shape
        return CodeStore(n=n, d=d, bits=32, packed=False,
                         data=vectors, params=None, base=base)

    @staticmethod
    def from_codes(
        codes: jax.Array,
        params: Qz.QuantParams,
        *,
        pack: bool = False,
        base: int = 0,
    ) -> "CodeStore":
        """Wrap already-encoded integer codes; optionally bit-pack int4."""
        n, d = codes.shape
        if pack:
            assert params.bits == 4, "packing is the 4-bit storage layout"
            if d % 2:
                codes = jnp.pad(codes, ((0, 0), (0, 1)))   # zero-code column
            codes = PK.pack_int4(codes)
        return CodeStore(n=n, d=d, bits=params.bits, packed=pack,
                         data=codes, params=params, base=base)

    @staticmethod
    def concat(stores: "list[CodeStore]", base: int = 0) -> "CodeStore":
        """Row-concatenate layout-compatible stores into one id space.

        The stream layer's segment-merge primitive: every input must agree
        on (d, bits, packed) and — for quantized stores — on the exact
        Eq. 1 constants, because a single store has a single code space;
        mixing differently-calibrated codes would silently mis-score.
        Input ``base`` offsets are discarded (rows are renumbered
        0..sum(n)-1 under the new ``base``).
        """
        if not stores:
            raise ValueError("CodeStore.concat of zero stores")
        head = stores[0]
        for s in stores[1:]:
            if (s.d, s.bits, s.packed) != (head.d, head.bits, head.packed):
                raise ValueError(
                    "concat of layout-incompatible stores: "
                    f"{(s.d, s.bits, s.packed)} vs {(head.d, head.bits, head.packed)}"
                )
            if not _params_equal(s.params, head.params):
                raise ValueError(
                    "concat of stores with different quantization constants "
                    "— one store has one code space; re-encode first "
                    "(stream compaction re-quantizes from raw payloads)"
                )
        data = jnp.concatenate([s.data for s in stores], axis=0)
        return CodeStore(n=sum(s.n for s in stores), d=head.d, bits=head.bits,
                         packed=head.packed, data=data, params=head.params,
                         base=base)

    def append(self, vectors: jax.Array) -> "CodeStore":
        """A new store with fp32 ``vectors`` encoded into this store's code
        space and appended (rows keep their order; ids extend n..n+m-1):
        grow a store under its existing constants without re-learning.
        """
        vectors = jnp.asarray(vectors, jnp.float32)
        if vectors.shape[1] != self.d:
            raise ValueError(f"append dim {vectors.shape[1]} != store d {self.d}")
        if not self.quantized:
            extra = CodeStore.dense(vectors)
        else:
            from repro.kernels import ops as K

            p = self.params
            codes = K.quantize(vectors, p.lo, p.hi, p.zero, bits=p.bits)
            extra = CodeStore.from_codes(codes, p, pack=self.packed)
        return CodeStore.concat([self, extra], base=self.base)

    # -- shape/metadata ----------------------------------------------------
    @property
    def quantized(self) -> bool:
        return self.bits < 32

    @property
    def d_eff(self) -> int:
        """Code width after the even-dim pad (== d unless packed odd-d)."""
        return self.data.shape[1] * 2 if self.packed else self.data.shape[1]

    @property
    def row_bytes(self) -> int:
        """Bytes of payload read to score one corpus row."""
        return int(self.data.shape[1]) * self.data.dtype.itemsize

    def memory_bytes(self) -> int:
        """Payload + Eq. 1 constants — the Table 1/2 memory column."""
        total = int(self.data.size) * self.data.dtype.itemsize
        if self.params is not None:
            total += 3 * self.d * 4                        # lo / hi / zero f32
        return total

    # -- views -------------------------------------------------------------
    def encode_queries(self, queries: jax.Array) -> jax.Array:
        """h(q) of Definition 2: map queries into the store's code space."""
        from repro.kernels import ops as K

        if not self.quantized:
            return jnp.asarray(queries, jnp.float32)
        p = self.params
        # the same Eq. 1 map as the corpus kernel, in XLA: a query batch
        # is too small to stream, and sharded plans encode replicated
        # queries, which XLA cannot partition around a Mosaic call
        q = K.quantize(queries, p.lo, p.hi, p.zero, bits=p.bits,
                       use_pallas=False)
        if self.packed and self.d_eff != self.d:
            q = jnp.pad(q, ((0, 0), (0, self.d_eff - self.d)))
        return q

    def unpacked(self) -> jax.Array:
        """Full-width payload view ([N, d_eff]); unpacks int4 on the fly."""
        return PK.unpack_int4(self.data) if self.packed else self.data

    def take(self, ids: jax.Array) -> jax.Array:
        """Gather rows by id, returned at full width (graph-walk path:
        gather the *packed* rows, then shift-mask only what was touched)."""
        rows = self.data[ids]
        return PK.unpack_int4(rows) if self.packed else rows

    # -- disk round-trip fragments ----------------------------------------
    def state(self, prefix: str = "") -> tuple[dict[str, Any], dict[str, Any]]:
        """Serializable (arrays, meta) fragments.

        ``prefix`` namespaces the array keys and the meta record
        (``{prefix}store``) so one npz can carry several stores — an
        index's scan store plus its rerank store (``prefix="rr_"``).
        """
        arrays: dict[str, Any] = {f"{prefix}data": self.data}
        meta: dict[str, Any] = {
            f"{prefix}store": {"n": self.n, "d": self.d, "bits": self.bits,
                               "packed": self.packed, "base": self.base,
                               "quant": None},
        }
        if self.params is not None:
            arrays.update({f"{prefix}q_lo": self.params.lo,
                           f"{prefix}q_hi": self.params.hi,
                           f"{prefix}q_zero": self.params.zero})
            meta[f"{prefix}store"]["quant"] = {"bits": self.params.bits,
                                               "scheme": self.params.scheme}
        return arrays, meta

    @staticmethod
    def from_state(
        arrays: dict[str, Any], meta: dict[str, Any], prefix: str = ""
    ) -> "CodeStore":
        sm = meta[f"{prefix}store"]
        params = None
        if sm["quant"] is not None:
            params = Qz.QuantParams(
                lo=jnp.asarray(arrays[f"{prefix}q_lo"]),
                hi=jnp.asarray(arrays[f"{prefix}q_hi"]),
                zero=jnp.asarray(arrays[f"{prefix}q_zero"]),
                bits=int(sm["quant"]["bits"]),
                scheme=str(sm["quant"]["scheme"]),
            )
        return CodeStore(
            n=int(sm["n"]), d=int(sm["d"]), bits=int(sm["bits"]),
            packed=bool(sm["packed"]), data=jnp.asarray(arrays[f"{prefix}data"]),
            params=params, base=int(sm["base"]),
        )


#: codeword index widths PQStore supports: 4-bit (16-codeword codebooks,
#: codes packed two per byte) or 8-bit (256 codewords, one byte per code)
PQ_CODE_BITS = (4, 8)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PQStore:
    """Product-quantization storage: codewords + per-subspace codebooks.

    ``bits`` is the codeword index width.  At 8 bits, ``codes`` is
    [N, M] uint8 into 256-codeword codebooks; at 4 bits, codebooks hold
    16 codewords and codes are bit-packed two per byte —
    [N, ceil(M/2)] uint8 via :func:`repro.core.pack.pack_uint4` (odd M
    pads a zero-code column; the ADC side pads its LUT with a zero
    subspace slice, so scores are unchanged) — which is why
    ``pq16x4`` reports exactly half the code bytes of ``pq16x8``.
    """

    n: int = dataclasses.field(metadata=dict(static=True))
    m: int = dataclasses.field(metadata=dict(static=True))       # subspaces
    lpq_tables: bool = dataclasses.field(metadata=dict(static=True))
    codes: jax.Array          # [N, M] uint8 | [N, ceil(M/2)] uint8 packed
    codebooks: jax.Array      # [M, 2^bits, d/M] f32
    bits: int = dataclasses.field(default=8, metadata=dict(static=True))

    def __post_init__(self):
        if self.bits not in PQ_CODE_BITS:
            raise ValueError(
                f"PQ codeword width must be one of {PQ_CODE_BITS} bits "
                f"(16- or 256-codeword codebooks), got {self.bits}"
            )

    @property
    def packed(self) -> bool:
        """Whether codes are stored two-per-byte (the 4-bit layout)."""
        return self.bits == 4

    @property
    def n_codewords(self) -> int:
        return 2 ** self.bits

    def unpacked_codes(self) -> jax.Array:
        """[N, M] codeword-index view; unpacks the 4-bit layout on the fly."""
        if not self.packed:
            return self.codes
        return PK.unpack_uint4(self.codes)[:, : self.m]

    @property
    def row_bytes(self) -> int:
        """Bytes of code payload read to score one corpus row."""
        return int(self.codes.shape[1])

    @property
    def code_bytes(self) -> int:
        """Bytes of the code matrix alone (the Table-1 codes column)."""
        return int(self.codes.size)

    def memory_bytes(self) -> int:
        return self.code_bytes + int(self.codebooks.size) * 4

    def state(self) -> tuple[dict[str, Any], dict[str, Any]]:
        arrays = {"codes": self.codes, "codebooks": self.codebooks}
        meta = {"store": {"n": self.n, "m": self.m, "bits": self.bits,
                          "lpq_tables": self.lpq_tables}}
        return arrays, meta

    @staticmethod
    def from_state(arrays: dict[str, Any], meta: dict[str, Any]) -> "PQStore":
        sm = meta["store"]
        return PQStore(
            n=int(sm["n"]), m=int(sm["m"]), lpq_tables=bool(sm["lpq_tables"]),
            codes=jnp.asarray(arrays["codes"]),
            codebooks=jnp.asarray(arrays["codebooks"]),
            bits=int(sm.get("bits", 8)),       # pre-PR-5 saves: 8-bit codes
        )
