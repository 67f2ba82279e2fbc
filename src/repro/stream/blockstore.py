"""Host-resident block store: the HDFS-block analogue of the paper's setting.

A `BlockStore` is a logical (n, d) row matrix cut into fixed-size row blocks
(the last block may be ragged). Blocks are produced on demand as numpy arrays —
from a resident array, from a generator function (synthetic data materializes
one block at a time instead of the full matrix), or from a memory-mapped file
on disk — so nothing larger than one block ever has to exist on the host
unless the backing itself is resident.

Stores compose: `shard(i, s)` restricts a store to a round-robin subset of
blocks (how a mesh data axis would split the stream across workers), and
`empty(...)` + `put(...)` give a writable store for staged outputs (e.g. the
embedded Y blocks of Algorithm 1).

Staged stores can hold their blocks in a compressed wire form (DESIGN.md §17):
a `CacheCodec` ("f32" passthrough, "bf16", per-column-scaled symmetric "int8")
encodes each block on `put` and decodes on `get`, so every existing consumer
keeps seeing f32 — while codec-aware consumers (the stream engine's producer,
the fused Lloyd plan) move the quantized `EncodedBlock` wire form to the
device instead and dequantize in VMEM. Both read paths share the global-id
`_read*` seam, so the unwritten-block guard protects them equally.

Sparse rows (DESIGN.md §18) are held as CSR: `BlockStore.from_csr` cuts a CSR
matrix into row blocks, each a `CSRBlock` of values, column ids and row
pointers with its nonzeros padded to ONE capacity for the whole store, so
every full block has one compiled shape. No dense block of the input is ever
built unless `materialize()` is asked for.
"""
from __future__ import annotations

from pathlib import Path
from typing import Callable, Iterator, NamedTuple

import jax
import numpy as np

LANE = 128  # TPU lane width
# A CSR store's nonzero capacity is a multiple of SLOT_TILE slots (32 lanes):
# on a TPU v5e, fits whose capacity had a small power-of-two factor (128 or
# 256 times an odd number) ran 8-13% slower than one at 77 x 4,096 (PERF.md).
SLOT_TILE = 32 * LANE


class EncodedBlock(NamedTuple):
    """One staged block in its codec's wire form.

    `payload` is the (rows, d) array in the codec's storage dtype (int8 /
    bfloat16), `scale` the dequantization factor — a (1, d) f32 row of
    per-COLUMN scales for int8, a scalar 1.0 for bf16. A NamedTuple so the pair
    is a jax pytree: `jax.device_put(EncodedBlock(...))` moves the compressed
    bytes, and `repro.kernels.ops.lloyd_step_plan` dequantizes
    `payload * scale` on device — the decoded f32 block never crosses the
    host->device link.
    """

    payload: np.ndarray
    scale: np.ndarray


class BlockHeader(NamedTuple):
    """Typed per-block metadata of a staged store: how block bytes decode."""

    codec: str  # "f32" | "bf16" | "int8"
    rows: int  # row count of this block (ragged final block < block_rows)
    d: int  # feature width
    scale: float  # dequant factor (1.0 for f32/bf16)


@jax.tree_util.register_pytree_node_class
class CSRBlock:
    """One block of sparse rows in CSR form.

    `values` (f32) and `cols` (int32) hold the block's nonzeros row by row,
    padded to `slots` (the store's capacity); `indptr` (rows + 1, int32)
    points into them. Padding slots sit after the last row's nonzeros and
    carry value 0 at column 0, so any sum over slots is unchanged by them.
    `d` and `nnz` (the real nonzeros) ride as static pytree data: the three
    arrays are the leaves, so `jax.device_put` moves the block whole and a
    jit over the leaves compiles once per (rows, slots).
    """

    __slots__ = ("values", "cols", "indptr", "d", "nnz")

    def __init__(self, values, cols, indptr, *, d: int, nnz: int):
        self.values, self.cols, self.indptr = values, cols, indptr
        self.d, self.nnz = int(d), int(nnz)

    def tree_flatten(self):
        return (self.values, self.cols, self.indptr), (self.d, self.nnz)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves, d=aux[0], nnz=aux[1])

    @classmethod
    def from_rows(cls, indptr, cols, values, *, d: int,
                  capacity: int | None = None) -> "CSRBlock":
        """Host block from CSR arrays (`indptr` may start anywhere: it is
        rebased to 0). `capacity=None` pads to `bucket(nnz)`."""
        indptr = np.asarray(indptr, np.int64)
        lo, hi = int(indptr[0]), int(indptr[-1])
        nnz = hi - lo
        cap = bucket(nnz) if capacity is None else int(capacity)
        if cap < nnz:
            raise ValueError(f"capacity {cap} < the block's {nnz} nonzeros")
        v = np.zeros(cap, np.float32)
        c = np.zeros(cap, np.int32)
        v[:nnz] = np.asarray(values[lo:hi], np.float32)
        c[:nnz] = np.asarray(cols[lo:hi], np.int32)
        return cls(v, c, (indptr - lo).astype(np.int32), d=d, nnz=nnz)

    @property
    def shape(self) -> tuple[int, int]:
        return (self.indptr.shape[0] - 1, self.d)

    @property
    def slots(self) -> int:
        return self.values.shape[0]

    @property
    def nbytes(self) -> int:
        return sum(int(a.nbytes) for a in (self.values, self.cols, self.indptr))

    def __getitem__(self, rows: slice) -> "CSRBlock":
        """The first rows of a host block, `block[:h]` (the seeding pool of
        a sample): the only slice a CSR block takes. The result is padded
        to its own `bucket`."""
        if not isinstance(rows, slice) or rows.start not in (None, 0) \
                or rows.step not in (None, 1):
            raise TypeError("a CSRBlock takes only a leading row slice [:h]")
        h = min(self.shape[0], self.shape[0] if rows.stop is None else rows.stop)
        return CSRBlock.from_rows(np.asarray(self.indptr)[:h + 1],
                                  np.asarray(self.cols), np.asarray(self.values),
                                  d=self.d)

    def row(self, r: int) -> tuple[np.ndarray, np.ndarray]:
        """(column ids, values) of row r of a host block."""
        lo, hi = int(self.indptr[r]), int(self.indptr[r + 1])
        return self.cols[lo:hi], self.values[lo:hi]

    def toarray(self, rows=None) -> np.ndarray:
        """Dense (rows, d) float32 on the host: every row, or the rows
        indexed by `rows`. Duplicate column ids add, as in any CSR."""
        indptr = np.asarray(self.indptr)
        cols, vals = np.asarray(self.cols), np.asarray(self.values)
        rows = np.arange(self.shape[0]) if rows is None else np.asarray(rows)
        out = np.zeros((rows.shape[0], self.d), np.float32)
        lens = indptr[rows + 1] - indptr[rows]
        at = np.repeat(indptr[rows], lens) + (
            np.arange(int(lens.sum())) - np.repeat(np.cumsum(lens) - lens, lens))
        np.add.at(out, (np.repeat(np.arange(rows.shape[0]), lens), cols[at]),
                  vals[at])
        return out


def bucket(nnz: int) -> int:
    """Slot capacity of a CSR row set built on its own (a reservoir sample,
    a seeding pool): the next power of two, at least one lane, so row sets
    of similar size share one compiled shape."""
    return max(LANE, 1 << max(int(nnz) - 1, 0).bit_length())


def is_csr(x) -> bool:
    """A CSR input: a `CSRBlockStore`, or a scipy.sparse matrix or array
    (any format; converted with `.tocsr()`)."""
    return isinstance(x, CSRBlockStore) or callable(getattr(x, "tocsr", None))


def _bf16_dtype() -> np.dtype:
    # ml_dtypes ships with jax; its bfloat16 is a registered numpy dtype, so
    # np.memmap / np.zeros work on it like any builtin type.
    import ml_dtypes

    return np.dtype(ml_dtypes.bfloat16)


class CacheCodec:
    """One staged-cache codec: block f32 <-> (payload, scale) wire form.

    The error bounds are the documented contract (DESIGN.md §17, asserted in
    tests/test_cache_codec.py):

      f32   passthrough; exact.
      bf16  elementwise relative error <= 2**-8 (bf16 keeps 8 significand
            bits); scale is identically 1.0.
      int8  per-COLUMN symmetric: scale_j = max|col_j| / 127 (clamped
            >= 1e-12), q = clip(round(block / scale), -127, 127). Rounding
            error is at most scale_j / 2, so elementwise
            |y - q * scale| <= max|col| / 254 — every feature keeps ~0.4%
            relative accuracy regardless of how its dynamic range compares
            to the rest (embedded Y columns are eigenvalue-scaled, so one
            shared scale would crush the small coordinates; row norms, by
            contrast, are nearly uniform).
    """

    def __init__(self, name: str, store_dtype):
        if name not in ("f32", "bf16", "int8"):
            raise ValueError(
                f"unknown cache codec {name!r}: expected 'f32', 'bf16' or 'int8'"
            )
        self.name = name
        self.store_dtype = np.dtype(store_dtype)

    def encode(self, block: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """f32 block -> (payload in store_dtype, dequant scale: a (1, d)
        per-column row for int8, scalar 1.0 otherwise)."""
        if self.name == "f32":
            return block, np.float32(1.0)
        if self.name == "bf16":
            return block.astype(self.store_dtype), np.float32(1.0)
        scale = np.maximum(
            np.max(np.abs(block), axis=0, keepdims=True) / 127.0, 1e-12
        ).astype(np.float32)
        q = np.clip(np.rint(block / scale), -127, 127).astype(np.int8)
        return q, scale

    def decode(self, payload: np.ndarray, scale) -> np.ndarray:
        """(payload, scale) -> the decoded f32 block (identity for f32)."""
        if self.name == "f32":
            return payload
        if self.name == "bf16":
            return payload.astype(np.float32)
        return payload.astype(np.float32) * np.asarray(scale, np.float32)

    def error_bound(self, block: np.ndarray) -> np.ndarray:
        """Elementwise bound on |decode(encode(block)) - block| (the
        documented contract above), as an array broadcastable to `block`."""
        if self.name == "f32":
            return np.zeros_like(block)
        if self.name == "bf16":
            return np.abs(block) * np.float32(2.0 ** -8)
        amax = np.max(np.abs(block), axis=0, keepdims=True)
        return np.broadcast_to(
            np.maximum(amax / 254.0, 1e-12), block.shape
        ).astype(np.float32)


CODECS = ("f32", "bf16", "int8")


def get_codec(name: str) -> CacheCodec:
    """The `CacheCodec` registered under `name` ("f32" | "bf16" | "int8")."""
    if name == "f32":
        return CacheCodec("f32", np.float32)
    if name == "bf16":
        return CacheCodec("bf16", _bf16_dtype())
    if name == "int8":
        return CacheCodec("int8", np.int8)
    raise ValueError(
        f"unknown cache codec {name!r}: expected one of {CODECS}"
    )


class BlockStore:
    """Fixed-size row blocks over a logical (n, d) float32 matrix.

    `get(i)` returns block i as a numpy array of shape (rows_i, d) where
    rows_i == block_rows except possibly for the final block.
    """

    def __init__(
        self,
        get: Callable[[int], np.ndarray],
        *,
        n: int,
        d: int,
        block_rows: int,
        dtype=np.float32,
        block_ids: tuple[int, ...] | None = None,
        codec: str = "f32",
        get_encoded: "Callable[[int], EncodedBlock] | None" = None,
    ):
        if block_rows <= 0:
            raise ValueError(f"block_rows must be positive, got {block_rows}")
        self._get = get
        self.n = int(n)
        self.d = int(d)
        self.block_rows = int(block_rows)
        self.dtype = np.dtype(dtype)  # LOGICAL dtype: what get() decodes to
        #: Storage codec of the staged backing ("f32" | "bf16" | "int8").
        #: get() always decodes; get_encoded() exposes the wire form.
        self.codec = str(codec)
        self._get_encoded = get_encoded
        total = -(-self.n // self.block_rows)  # ceil div
        self._block_ids = tuple(range(total)) if block_ids is None else tuple(block_ids)

    # -- shape / iteration --------------------------------------------------

    @property
    def num_blocks(self) -> int:
        return len(self._block_ids)

    def rows_of(self, i: int) -> int:
        """Row count of local block i (handles the ragged final block)."""
        gid = self._block_ids[i]
        return min(self.block_rows, self.n - gid * self.block_rows)

    def block_id(self, i: int) -> int:
        """Global block id of local block i (differs after shard())."""
        return self._block_ids[i]

    def row_offset(self, i: int) -> int:
        """Global row index of the first row of local block i."""
        return self._block_ids[i] * self.block_rows

    def _read(self, gid: int) -> np.ndarray:
        """Backing read by GLOBAL block id. Derived stores (`shard`,
        `map_rows`) close over the parent's bound `_read`, so subclasses that
        guard reads (e.g. WritableBlockStore's unwritten-block check) keep
        their guard in every derived view."""
        return np.asarray(self._get(gid))

    def _read_encoded(self, gid: int) -> EncodedBlock | None:
        """Wire-form read by GLOBAL block id: the codec payload + scale, or
        None when the store has no encoded backing (codec "f32", or an f32
        derived view). Lives on the same global-id seam as `_read`, so guarded
        subclasses protect both paths and `shard()` views inherit both."""
        if self._get_encoded is None:
            return None
        return self._get_encoded(gid)

    def get(self, i: int) -> np.ndarray:
        if not 0 <= i < self.num_blocks:
            raise IndexError(f"block {i} out of range [0, {self.num_blocks})")
        blk = self._read(self._block_ids[i])
        expect = (self.rows_of(i), self.d)
        if blk.shape != expect:
            raise ValueError(f"block {i}: backing returned {blk.shape}, want {expect}")
        return blk

    def get_encoded(self, i: int) -> EncodedBlock | None:
        """Local block i in codec wire form (no decode, no f32 copy), or None
        when the store stages plain f32. The cheap host->device path: the
        engine ships the payload + scale and the Lloyd plan dequantizes on
        device."""
        if not 0 <= i < self.num_blocks:
            raise IndexError(f"block {i} out of range [0, {self.num_blocks})")
        return self._read_encoded(self._block_ids[i])

    def header(self, i: int) -> BlockHeader:
        """Typed header of local block i: codec, shape, and the block's
        LARGEST dequant step (max over the per-row scale column — the
        block-level error magnitude at a glance)."""
        enc = self.get_encoded(i)
        scale = float(np.max(enc.scale)) if enc is not None else 1.0
        return BlockHeader(
            codec=self.codec, rows=self.rows_of(i), d=self.d, scale=scale
        )

    def __iter__(self) -> Iterator[np.ndarray]:
        return (self.get(i) for i in range(self.num_blocks))

    def __len__(self) -> int:
        return self.num_blocks

    # -- composition --------------------------------------------------------

    def shard(self, index: int, num_shards: int) -> "BlockStore":
        """Round-robin block subset for worker `index` of `num_shards` — the
        block->mapper placement a mesh data axis induces."""
        if not 0 <= index < num_shards:
            raise ValueError(f"shard index {index} out of range for {num_shards}")
        ids = self._block_ids[index::num_shards]
        # Both read seams propagate as BOUND methods, so a guarded parent
        # (WritableBlockStore) keeps guarding, and a codec parent keeps
        # serving wire-form reads, through every derived view.
        return BlockStore(
            self._read, n=self.n, d=self.d, block_rows=self.block_rows,
            dtype=self.dtype, block_ids=ids,
            codec=self.codec, get_encoded=self._read_encoded,
        )

    def map_rows(self, fn: Callable[[np.ndarray], np.ndarray], d_out: int) -> "BlockStore":
        """Lazy per-block host transform (e.g. column select); same blocking.
        `fn` sees DECODED f32 blocks, so the derived view is a plain f32 store
        (the transform output has no codec wire form)."""
        return BlockStore(
            lambda gid: np.asarray(fn(self._read(gid))),
            n=self.n, d=d_out, block_rows=self.block_rows,
            dtype=self.dtype, block_ids=self._block_ids,
        )

    def materialize(self) -> np.ndarray:
        """Concatenate every block — tests/small data only, defeats the point."""
        return np.concatenate([self.get(i) for i in range(self.num_blocks)], axis=0)

    # -- constructors -------------------------------------------------------

    @classmethod
    def from_array(cls, X, block_rows: int) -> "BlockStore":
        """View a resident (n, d) array as blocks (zero-copy slices)."""
        X = np.asarray(X)
        n, d = X.shape
        return cls(
            lambda i: X[i * block_rows: (i + 1) * block_rows],
            n=n, d=d, block_rows=block_rows, dtype=X.dtype,
        )

    @classmethod
    def from_csr(cls, X, block_rows: int, *,
                 capacity: int | None = None) -> "CSRBlockStore":
        """View a CSR matrix (a scipy.sparse matrix or array, or anything
        with `indptr`, `indices`, `data` and `shape`) as blocks of
        `CSRBlock`s; nothing is densified. `capacity` gives the blocks more
        slots than the store's own rule (`CSRBlockStore`), so that another
        store shares their compiled shape."""
        if callable(getattr(X, "tocsr", None)):
            X = X.tocsr()
        n, d = X.shape
        return CSRBlockStore(X.indptr, X.indices, X.data, n=n, d=d,
                             block_rows=block_rows, capacity=capacity)

    @classmethod
    def from_generator(
        cls, make_block: Callable[[int], np.ndarray], *,
        n: int, d: int, block_rows: int, dtype=np.float32,
    ) -> "BlockStore":
        """Blocks produced on demand by `make_block(block_id)`; the function
        must be deterministic per id (blocks are re-requested across Lloyd
        iterations)."""
        return cls(make_block, n=n, d=d, block_rows=block_rows, dtype=dtype)

    @classmethod
    def from_memmap(
        cls, path: str | Path, *, d: int, block_rows: int, dtype=np.float32,
        codec: str = "f32", scales=None,
    ) -> "BlockStore":
        """Blocks read from a flat row-major binary file via np.memmap — the
        page cache is the only resident state.

        `codec=` reads a compressed staged cache back (the sweep stage's
        persisted Y payload): the file holds the codec's storage dtype and
        `scales` supplies the (num_blocks, d) per-block, per-column dequant
        rows (required for "int8", ignored for "bf16" whose scale is
        identically 1.0). Reads decode to f32; `get_encoded` serves the wire
        form straight off the memmap."""
        path = Path(path)
        codec_obj = get_codec(codec)
        store_dtype = codec_obj.store_dtype if codec != "f32" else np.dtype(dtype)
        itemsize = store_dtype.itemsize
        size = path.stat().st_size
        ragged = size % (d * itemsize)
        if ragged:
            raise ValueError(
                f"{path}: size {size} bytes is not a multiple of "
                f"d * itemsize = {d} * {itemsize}; {ragged} ragged trailing "
                "bytes (truncated file, or wrong d/dtype/codec?)"
            )
        n = size // (d * itemsize)
        mm = np.memmap(path, dtype=store_dtype, mode="r", shape=(n, d))
        if codec == "f32":
            return cls(
                lambda i: np.asarray(mm[i * block_rows: (i + 1) * block_rows]),
                n=n, d=d, block_rows=block_rows, dtype=dtype,
            )
        num_blocks = -(-n // block_rows)
        if codec == "int8" and scales is None:
            raise ValueError(f"{path}: codec 'int8' needs per-column scales=")
        sc = (np.ones((num_blocks, d), np.float32) if scales is None
              else np.asarray(scales, np.float32))
        if sc.shape != (num_blocks, d):
            raise ValueError(
                f"{path}: scales shape {np.shape(scales)} does not match "
                f"({num_blocks}, {d})"
            )

        def _enc(i: int) -> EncodedBlock:
            lo, hi = i * block_rows, (i + 1) * block_rows
            if codec == "bf16":
                return EncodedBlock(np.asarray(mm[lo:hi]), np.float32(1.0))
            return EncodedBlock(np.asarray(mm[lo:hi]), sc[i:i + 1])

        return cls(
            lambda i: codec_obj.decode(
                np.asarray(mm[i * block_rows: (i + 1) * block_rows]),
                sc[i:i + 1],
            ),
            n=n, d=d, block_rows=block_rows, dtype=dtype, codec=codec,
            get_encoded=_enc,
        )

    @classmethod
    def empty(
        cls, *, n: int, d: int, block_rows: int, dtype=np.float32,
        codec: str = "f32",
    ) -> "WritableBlockStore":
        """Writable store backed by one preallocated host array (staging area
        for per-block outputs, e.g. embedded Y blocks or label vectors).
        `codec=` stages blocks compressed (DESIGN.md §17)."""
        return WritableBlockStore(
            n=n, d=d, block_rows=block_rows, dtype=dtype, codec=codec
        )


class WritableBlockStore(BlockStore):
    """A BlockStore whose blocks are filled by `put(i, block)`.

    With a compressed `codec`, `put` encodes the f32 block into the staging
    buffer's wire form (int8 / bf16 + per-block scale) and `get` decodes back
    to f32 — a transparent round-trip for every existing consumer, within the
    codec's documented error bound. `get_encoded` reads the wire form without
    decoding. The unwritten-block guard sits on the shared global-id seam, so
    BOTH read paths (and every shard() view of either) raise on a block this
    store never staged.
    """

    def __init__(self, *, n: int, d: int, block_rows: int, dtype=np.float32,
                 codec: str = "f32"):
        self._cache_codec = get_codec(codec)
        buf_dtype = (self._cache_codec.store_dtype if codec != "f32"
                     else np.dtype(dtype))
        self._buf = np.zeros((n, d), dtype=buf_dtype)
        num_blocks = -(-n // block_rows)
        self._filled = np.zeros(num_blocks, dtype=bool)
        # per-block, per-COLUMN dequant rows (int8); all-ones for f32/bf16
        self._scales = np.ones((num_blocks, d), dtype=np.float32)
        super().__init__(
            lambda i: self._cache_codec.decode(
                self._buf[i * block_rows: (i + 1) * block_rows],
                self._scales[i:i + 1],
            ),
            n=n, d=d, block_rows=block_rows, dtype=dtype, codec=codec,
        )

    def put(self, i: int, block: np.ndarray) -> None:
        lo = i * self.block_rows
        hi = lo + min(self.block_rows, self.n - lo)
        block = np.asarray(block)
        if block.shape != (hi - lo, self.d):
            raise ValueError(f"put block {i}: got {block.shape}, want {(hi - lo, self.d)}")
        payload, scale = self._cache_codec.encode(block)
        self._buf[lo:hi] = payload
        self._scales[i] = scale  # scalar 1.0 broadcasts for f32/bf16
        self._filled[i] = True

    def _read(self, gid: int) -> np.ndarray:
        # The guard lives on the global-id read path so shard()/map_rows()
        # views inherit it: an unwritten block must never silently read as
        # zeros (a sharded staged-Y store would cluster garbage).
        if not self._filled[gid]:
            raise ValueError(f"block {gid} read before it was written")
        return super()._read(gid)

    def _read_encoded(self, gid: int) -> EncodedBlock | None:
        if self.codec == "f32":
            return None
        if not self._filled[gid]:  # same guard as the decoded path
            raise ValueError(f"block {gid} read before it was written")
        lo = gid * self.block_rows
        hi = lo + min(self.block_rows, self.n - lo)
        if self.codec == "bf16":  # scale identically 1.0: don't ship a row
            return EncodedBlock(self._buf[lo:hi], np.float32(1.0))
        return EncodedBlock(self._buf[lo:hi], self._scales[gid:gid + 1])

    def staged_nbytes(self, gid: int) -> int:
        """Bytes block `gid` occupies in the staging buffer (wire size,
        including its per-column scale row for int8)."""
        rows = min(self.block_rows, self.n - gid * self.block_rows)
        extra = self.d * self._scales.itemsize if self.codec == "int8" else 0
        return rows * self.d * self._buf.itemsize + extra

    @property
    def nbytes_staged(self) -> int:
        """Total bytes of the staging buffer (+ the scale rows for int8)."""
        extra = self._scales.nbytes if self.codec == "int8" else 0
        return self._buf.nbytes + extra


class CSRBlockStore(BlockStore):
    """Row blocks of a host CSR matrix; `get(i)` is a `CSRBlock`.

    Capacity rule: every block's nonzeros are padded to `nnz_capacity`, the
    store's largest block rounded up to a multiple of `SLOT_TILE`, so every
    full block (and the short last one, by its rows) has one compiled shape.
    A caller may ask for more (`capacity=`, at least the largest block) to
    share a shape with another store. `materialize()` densifies, and only it does.
    """

    def __init__(self, indptr, indices, data, *, n: int, d: int,
                 block_rows: int, capacity: int | None = None,
                 block_ids: tuple[int, ...] | None = None):
        self._indptr = np.asarray(indptr, np.int64)
        self._indices = np.asarray(indices, np.int32)
        self._data = np.asarray(data, np.float32)
        if self._indptr.shape != (n + 1,):
            raise ValueError(f"indptr has shape {self._indptr.shape}, want ({n + 1},)")
        super().__init__(self._block, n=n, d=d, block_rows=block_rows,
                         block_ids=block_ids)
        edges = self._indptr[np.minimum(
            np.arange(0, -(-n // self.block_rows) + 1) * self.block_rows, n)]
        largest = int(np.max(np.diff(edges), initial=0))
        rule = max(SLOT_TILE, -(-largest // SLOT_TILE) * SLOT_TILE)
        if capacity is not None and capacity < largest:
            raise ValueError(
                f"capacity {capacity} is below the largest block's {largest} "
                "nonzeros")
        self.nnz_capacity = rule if capacity is None else int(capacity)

    @property
    def nnz(self) -> int:
        """Real nonzeros of the whole (unsharded) matrix."""
        return int(self._indptr[-1] - self._indptr[0])

    def _block(self, gid: int) -> CSRBlock:
        lo = gid * self.block_rows
        hi = min(lo + self.block_rows, self.n)
        return CSRBlock.from_rows(self._indptr[lo:hi + 1], self._indices,
                                  self._data, d=self.d,
                                  capacity=self.nnz_capacity)

    def _read(self, gid: int) -> CSRBlock:
        return self._block(gid)

    def shard(self, index: int, num_shards: int) -> "CSRBlockStore":
        if not 0 <= index < num_shards:
            raise ValueError(f"shard index {index} out of range for {num_shards}")
        return CSRBlockStore(
            self._indptr, self._indices, self._data, n=self.n, d=self.d,
            block_rows=self.block_rows, capacity=self.nnz_capacity,
            block_ids=self._block_ids[index::num_shards])

    def map_rows(self, fn, d_out):
        raise NotImplementedError("map_rows over CSR blocks is not supported")

    def materialize(self) -> np.ndarray:
        """Every block densified and concatenated: (rows, d) float32. Tests
        and small data only; an RCV1-sized store is 36.6 GB dense."""
        return np.concatenate([self.get(i).toarray()
                               for i in range(self.num_blocks)], axis=0)
