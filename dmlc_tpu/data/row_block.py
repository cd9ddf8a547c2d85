"""Sparse row blocks: the CSR batch unit parsers emit.

Equivalent of reference include/dmlc/data.h (Row data.h:74-162, RowBlock
data.h:175-236) and src/data/row_block.h (RowBlockContainer). Arrays are
numpy (host); the device shim (:mod:`dmlc_tpu.data.device`) converts blocks
to jax BCOO / padded-dense without another copy where possible.

Layout (CSR):
    offset  int64[n+1]   row i spans index/value[offset[i]:offset[i+1]]
    label   float32[n]
    weight  float32[n]   optional (None = unweighted, data.h:91)
    qid     int64[n]     optional query ids (data.h:93)
    field   index[nnz]   optional libfm field ids (data.h:102)
    index   uint32/uint64[nnz]  feature ids
    value   float32[nnz] optional (None = binary features, data.h:106);
            int32 / int64 for a CSV parsed with an integer ``dtype=``
"""

from __future__ import annotations

from typing import BinaryIO, Iterator, List, Optional

import numpy as np

from dmlc_tpu.utils import serializer as ser
from dmlc_tpu.utils.check import DMLCError, check


class Row:
    """One sparse row view — analog of dmlc::Row (data.h:74-162)."""

    __slots__ = ("label", "weight", "qid", "field", "index", "value")

    def __init__(self, label, weight, qid, field, index, value):
        self.label = label
        self.weight = weight
        self.qid = qid
        self.field = field
        self.index = index
        self.value = value

    def __len__(self) -> int:
        return len(self.index)

    def get_value(self, i: int) -> float:
        """value of the i-th entry; binary features read as 1 (data.h:132)."""
        return 1.0 if self.value is None else float(self.value[i])

    def sdot(self, weight_vec: np.ndarray) -> float:
        """Sparse dot with a dense weight vector (Row::SDot, data.h:146-161)."""
        w = weight_vec[self.index]
        if self.value is None:
            return float(np.sum(w))
        return float(np.dot(w, self.value))


INTEGER_VALUE_DTYPES = (np.dtype(np.int32), np.dtype(np.int64))


def _value_array(value) -> np.ndarray:
    """``RowBlock.value`` as stored: an int32 / int64 array stays what it
    is (the cells of a CSV parsed with ``dtype=int32|int64``: ids, which a
    float32 would round above 2**24), anything else is float32."""
    if isinstance(value, np.ndarray) and value.dtype in INTEGER_VALUE_DTYPES:
        return value
    return np.asarray(value, dtype=np.float32)


class RowBlock:
    """CSR batch — analog of dmlc::RowBlock (data.h:175-236). ``value`` is
    float32, or int32 / int64 where the parser was asked for integer cells
    (:func:`_value_array`); slices, merges, the block cache's segments and
    the binary round trip keep that dtype."""

    def __init__(
        self,
        offset: np.ndarray,
        label: np.ndarray,
        index: np.ndarray,
        value: Optional[np.ndarray] = None,
        weight: Optional[np.ndarray] = None,
        qid: Optional[np.ndarray] = None,
        field: Optional[np.ndarray] = None,
        hold=None,
    ):
        # `hold` pins foreign buffer owners (the native core's malloc'd
        # results) for as long as this block's views are alive
        self.hold = hold
        self.offset = np.asarray(offset, dtype=np.int64)
        self.label = np.asarray(label, dtype=np.float32)
        self.index = np.asarray(index)
        self.value = None if value is None else _value_array(value)
        self.weight = None if weight is None else np.asarray(weight, dtype=np.float32)
        self.qid = None if qid is None else np.asarray(qid, dtype=np.int64)
        self.field = None if field is None else np.asarray(field)
        n = len(self.label)
        check(len(self.offset) == n + 1, "RowBlock: offset must have size n+1")
        nnz = int(self.offset[-1])
        check(len(self.index) == nnz, "RowBlock: index size mismatch with offset[-1]")
        for name in ("value",):
            arr = getattr(self, name)
            if arr is not None:
                check(len(arr) == nnz, f"RowBlock: {name} size mismatch")
        for name in ("weight", "qid"):
            arr = getattr(self, name)
            if arr is not None:
                check(len(arr) == n, f"RowBlock: {name} size mismatch")

    def __len__(self) -> int:
        return len(self.label)

    @property
    def num_nonzero(self) -> int:
        return int(self.offset[-1])

    @property
    def num_col(self) -> int:
        """max feature id + 1 (what downstream sizes weight vectors with)."""
        return int(self.index.max()) + 1 if len(self.index) else 0

    def __getitem__(self, i):
        """Row view (RowBlock::operator[], data.h:365-394); a slice returns
        the :meth:`slice` sub-block, so ``block[10:20]`` reads naturally."""
        if isinstance(i, slice):
            check(i.step in (None, 1), "RowBlock: stepped slices unsupported")
            begin, end, _ = i.indices(len(self))
            return self.slice(begin, max(begin, end))
        if i < 0:
            i += len(self)
        check(0 <= i < len(self), f"RowBlock: row {i} out of range")
        s, e = int(self.offset[i]), int(self.offset[i + 1])
        return Row(
            float(self.label[i]),
            float(self.weight[i]) if self.weight is not None else 1.0,
            int(self.qid[i]) if self.qid is not None else None,
            self.field[s:e] if self.field is not None else None,
            self.index[s:e],
            self.value[s:e] if self.value is not None else None,
        )

    def __iter__(self) -> Iterator[Row]:
        for i in range(len(self)):
            yield self[i]

    def slice(self, begin: int, end: int) -> "RowBlock":
        """Sub-block of rows [begin, end) (RowBlock::Slice, data.h:216)."""
        check(0 <= begin <= end <= len(self), "RowBlock.slice: bad range")
        s, e = int(self.offset[begin]), int(self.offset[end])
        return RowBlock(
            offset=self.offset[begin:end + 1] - s,
            label=self.label[begin:end],
            index=self.index[s:e],
            value=self.value[s:e] if self.value is not None else None,
            weight=self.weight[begin:end] if self.weight is not None else None,
            qid=self.qid[begin:end] if self.qid is not None else None,
            field=self.field[s:e] if self.field is not None else None,
            hold=self.hold,
        )

    def mem_cost_bytes(self) -> int:
        """Approximate memory cost (RowBlock::MemCostBytes, data.h:203)."""
        cost = self.offset.nbytes + self.label.nbytes + self.index.nbytes
        for arr in (self.value, self.weight, self.qid, self.field):
            if arr is not None:
                cost += arr.nbytes
        return cost

    def to_dense(self, num_col: Optional[int] = None) -> np.ndarray:
        """Densify to [n, num_col] float32, or the values' own dtype where
        they are integer cells (feeds the padded-dense device path)."""
        ncol = num_col if num_col is not None else self.num_col
        integer = self.value is not None and self.value.dtype.kind == "i"
        out = np.zeros((len(self), ncol),
                       dtype=self.value.dtype if integer else np.float32)
        rows = np.repeat(np.arange(len(self)), np.diff(self.offset))
        vals = self.value if self.value is not None else np.ones(len(self.index), np.float32)
        keep = self.index < ncol
        out[rows[keep], self.index[keep]] = vals[keep]
        return out

    # -- columnar segment round trip (io/block_cache.py format) --

    def to_segments(self) -> dict:
        """The block's arrays as the named columnar segments the block
        cache serializes (:mod:`dmlc_tpu.io.block_cache` SEGMENT_NAMES);
        absent optional arrays map to None."""
        return {
            "offset": self.offset, "label": self.label, "weight": self.weight,
            "qid": self.qid, "field": self.field, "index": self.index,
            "value": self.value,
        }

    @staticmethod
    def from_segments(segments: dict, hold=None) -> "RowBlock":
        """Rebuild a block from :meth:`to_segments` output. Segment dtypes
        already match the block layout, so mmap-backed views pass through
        zero-copy; ``hold`` pins their buffer owner (the reader's mmap)."""
        return RowBlock(
            offset=segments["offset"], label=segments["label"],
            index=segments["index"], value=segments.get("value"),
            weight=segments.get("weight"), qid=segments.get("qid"),
            field=segments.get("field"), hold=hold,
        )

    # -- binary round trip (row_block.h:189-215) --

    def save(self, stream: BinaryIO) -> None:
        payload = {
            "offset": self.offset, "label": self.label, "index": self.index,
            "value": self.value, "weight": self.weight, "qid": self.qid,
            "field": self.field,
        }
        ser.write_obj(stream, {k: v for k, v in payload.items()})

    @staticmethod
    def load(stream: BinaryIO) -> "RowBlock":
        d = ser.read_obj(stream)
        return RowBlock(
            offset=d["offset"], label=d["label"], index=d["index"],
            value=d["value"], weight=d["weight"], qid=d["qid"], field=d["field"],
        )


class DenseBlock:
    """A parsed batch already in the dense device layout [n, num_col].

    Emitted by parsers in dense mode (``set_emit_dense``) — the TPU-first
    fast path that skips CSR materialization entirely; the reference has no
    analog (its parsers always build CSR RowBlocks, src/data/row_block.h).
    """

    __slots__ = ("x", "label", "weight", "hold", "resume_state", "packed",
                 "device_span", "trace_ctx")

    def __init__(self, x: np.ndarray, label: np.ndarray,
                 weight: Optional[np.ndarray] = None, hold=None,
                 packed: bool = False):
        # packed: x is [n, num_col + 2] with label/weight as the trailing
        # columns (label/weight here alias those columns as views) — the
        # device path ships the ONE packed array (api.h DenseResult docs)
        self.x = x
        self.label = label
        self.weight = weight
        self.hold = hold
        self.packed = packed
        self.resume_state = None  # parser position just after this block
        # optional (service snapshot frames): the block's verbatim
        # container bytes + span layout + stored kind, for a
        # device_decode=True DeviceIter to decode in HBM instead of
        # shipping the host-decoded views (ops/device_decode)
        self.device_span = None
        # optional (service clients): the (trace_id, span_id) context of
        # the grant that produced this block (docs/observability.md)
        self.trace_ctx = None

    def __len__(self) -> int:
        return len(self.label)

    def slice(self, begin: int, end: int) -> "DenseBlock":
        """Row range view [begin, end), mirroring RowBlock.slice."""
        return DenseBlock(
            self.x[begin:end], self.label[begin:end],
            self.weight[begin:end] if self.weight is not None else None,
            hold=self.hold, packed=self.packed)


class CooBlock:
    """A parsed batch already in device-ready COO layout.

    Emitted by parsers in COO mode (``set_emit_coo``) — coordinates are
    int32 [nnz_padded, 2] (row, col) with OOB padding, ``values`` is None
    when the block is all-ones and elision is on (the device synthesizes
    them), and label/weight carry the bucket-padded row dim. The native
    pass assembles these off-GIL, replacing the numpy coordinate assembly
    of ops.sparse.block_to_bcoo_host on the convert thread. ``n_rows`` and
    ``nnz`` are the REAL counts. No reference analog (its parsers always
    build CSR, src/data/row_block.h); this is the TPU-first sparse path.
    """

    __slots__ = ("coords", "values", "label", "weight", "n_rows", "nnz",
                 "num_col", "hold", "resume_state", "row_ptr",
                 "trace_ctx")

    def __init__(self, coords: np.ndarray, values: Optional[np.ndarray],
                 label: np.ndarray, weight: np.ndarray, n_rows: int,
                 nnz: int, num_col: int, hold=None,
                 row_ptr: Optional[np.ndarray] = None):
        # csr_wire blocks: coords is cols-only [nnz_padded] and row_ptr is
        # [rows_padded + 1]; the device consumer rebuilds (row, col) pairs
        self.row_ptr = row_ptr
        self.coords = coords
        self.values = values
        self.label = label
        self.weight = weight
        self.n_rows = n_rows
        self.nnz = nnz
        self.num_col = num_col
        self.hold = hold
        self.resume_state = None
        self.trace_ctx = None

    @property
    def shape(self):
        """BCOO dense shape: (padded rows, declared width)."""
        return (len(self.label), self.num_col)

    def __len__(self) -> int:
        return self.n_rows


class RowBlockContainer:
    """Growable RowBlock accumulator — analog of src/data/row_block.h.

    Parsers append per-chunk numpy arrays; ``to_block`` concatenates once.
    """

    def __init__(self, index_dtype=np.uint64):
        self.index_dtype = index_dtype
        self._offsets: List[np.ndarray] = []
        self._labels: List[np.ndarray] = []
        self._indices: List[np.ndarray] = []
        self._values: List[Optional[np.ndarray]] = []
        self._weights: List[Optional[np.ndarray]] = []
        self._qids: List[Optional[np.ndarray]] = []
        self._fields: List[Optional[np.ndarray]] = []
        self._holds: List = []  # buffer owners of pushed zero-copy views
        self.max_index = 0

    def push_block(self, block: RowBlock) -> None:
        if len(block) == 0:
            return
        if block.hold is not None:
            # the stored arrays are views over the block's foreign buffers;
            # keep their owner alive for the container's lifetime
            self._holds.append(block.hold)
        self._offsets.append(np.diff(block.offset))
        self._labels.append(block.label)
        self._indices.append(block.index)
        self._values.append(block.value)
        self._weights.append(block.weight)
        self._qids.append(block.qid)
        self._fields.append(block.field)
        if len(block.index):
            self.max_index = max(self.max_index, int(block.index.max()))

    def push_row(
        self, label: float, index, value=None, weight=None, qid=None, field=None
    ) -> None:
        index = np.asarray(index, dtype=self.index_dtype)
        self._offsets.append(np.array([len(index)], dtype=np.int64))
        self._labels.append(np.array([label], dtype=np.float32))
        self._indices.append(index)
        self._values.append(None if value is None else np.asarray(value, np.float32))
        self._weights.append(None if weight is None else np.array([weight], np.float32))
        self._qids.append(None if qid is None else np.array([qid], np.int64))
        self._fields.append(None if field is None else np.asarray(field, self.index_dtype))
        if len(index):
            self.max_index = max(self.max_index, int(index.max()))

    def __len__(self) -> int:
        return sum(len(l) for l in self._labels)

    def clear(self) -> None:
        self.__init__(self.index_dtype)

    @staticmethod
    def _cat_optional(parts: List[Optional[np.ndarray]], sizes: List[int], dtype):
        """Concatenate optional per-chunk arrays; missing chunks get defaults."""
        if all(p is None for p in parts):
            return None
        filled = []
        for p, n in zip(parts, sizes):
            if p is None:
                filled.append(np.ones(n, dtype) if dtype == np.float32 else np.zeros(n, dtype))
            else:
                filled.append(p)
        return np.concatenate(filled) if filled else None

    def to_block(self) -> RowBlock:
        if not self._labels:
            empty_idx = np.empty(0, dtype=self.index_dtype)
            return RowBlock(np.zeros(1, np.int64), np.empty(0, np.float32), empty_idx)
        row_counts = [len(l) for l in self._labels]
        nnz_counts = [len(i) for i in self._indices]
        offset = np.concatenate([[0], np.cumsum(np.concatenate(self._offsets))])
        label = np.concatenate(self._labels)
        index = np.concatenate(self._indices).astype(self.index_dtype, copy=False)
        value = self._cat_optional(self._values, nnz_counts, np.float32)
        weight = self._cat_optional(self._weights, row_counts, np.float32)
        qid = self._cat_optional(self._qids, row_counts, np.int64)
        field = self._cat_optional(self._fields, nnz_counts, self.index_dtype)
        return RowBlock(offset, label, index, value, weight, qid, field)

    def save(self, stream: BinaryIO) -> None:
        self.to_block().save(stream)

    @staticmethod
    def load(stream: BinaryIO) -> RowBlock:
        return RowBlock.load(stream)
