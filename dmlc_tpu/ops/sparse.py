"""Sparse batch layouts for TPU + the products over them.

Three device layouts for a parsed RowBlock (host CSR):

- **padded dense** ``[B, D]`` — right for low-dim dense-ish data (HIGGS,
  Criteo after hashing): one bf16/f32 matmul on the MXU beats any sparse
  gather at D up to a few thousand.
- **ELL** ``indices/values [B, K]`` (rows padded to K nonzeros with a
  sentinel) — right for high-dim sparse data (KDD2012): static shapes, XLA
  turns the gather+reduce into vectorized ops; a Pallas kernel covers the
  matvec when K is large.
- **BCOO** (jax.experimental.sparse) — interop layout for downstream jax
  code that wants a real sparse type.

The reference's only sparse op is Row::SDot (data.h:146-161) feeding linear
learners; ``ell_matvec`` is its batched TPU analog.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dmlc_tpu.data.row_block import RowBlock
from dmlc_tpu.utils.check import DMLCError


class EllBatch(NamedTuple):
    """Row-padded sparse batch: the TPU-friendly static-shape layout.

    indices: int32 [B, K] — feature ids, ``D`` (=num_col) marks padding
    values:  float32 [B, K] — zeros at padding
    label:   float32 [B]
    weight:  float32 [B] — ones when the source had no weights
    fields:  optional unsigned [B, K] — the libfm field id of every slot
             (``RowBlock.field``, data.h:102), 0 at padding; ``None``
             unless the batch was built with ``fields=True``
    """

    indices: jax.Array | np.ndarray
    values: jax.Array | np.ndarray
    label: jax.Array | np.ndarray
    weight: jax.Array | np.ndarray
    fields: Optional[jax.Array | np.ndarray] = None

    @property
    def batch_size(self) -> int:
        return self.indices.shape[0]

    @property
    def max_nnz(self) -> int:
        return self.indices.shape[1]


def _row_lengths(block: RowBlock) -> np.ndarray:
    return np.diff(block.offset)


def field_plane_dtype(max_field: int) -> np.dtype:
    """The narrowest unsigned dtype that holds field ids up to
    ``max_field``."""
    for dt in (np.uint8, np.uint16, np.uint32):
        if max_field <= np.iinfo(dt).max:
            return np.dtype(dt)
    raise DMLCError(f"field id {max_field} does not fit 32 bits")


def ell_truncated_slots(block: RowBlock, max_nnz: Optional[int]) -> int:
    """Non-zeros :func:`block_to_ell` cuts from ``block`` at ``max_nnz``
    slots a row (0 with no ``max_nnz``: K is then the longest row)."""
    if max_nnz is None or not len(block):
        return 0
    return int(np.maximum(_row_lengths(block) - max(int(max_nnz), 1), 0).sum())


def block_to_ell(
    block: RowBlock,
    num_col: int,
    max_nnz: Optional[int] = None,
    pad_rows_to: Optional[int] = None,
    fields: bool = False,
) -> EllBatch:
    """CSR -> ELL with numpy masked fills (host side, zero Python loops).

    Rows longer than ``max_nnz`` are truncated (callers pick K as the
    dataset's true max row length to avoid that; :func:`ell_truncated_slots`
    counts what is cut); short rows pad with
    index=num_col, value=0. ``pad_rows_to`` pads the batch dimension with
    empty zero-weight rows so every batch has one static shape — XLA then
    compiles the downstream step exactly once. ``fields=True`` adds the
    field plane from ``block.field`` (libfm text), slot for slot beside
    ``indices``, in the narrowest unsigned dtype that holds the block's
    largest field id (at least uint8; a stream's batches share a dtype as
    long as its field ids stay under 256), 0 at padding; a block without a
    field column is a checked error.
    """
    if fields and block.field is None:
        raise DMLCError(
            "block_to_ell(fields=True): the source's blocks carry no field "
            "column (only the libfm format has one)")
    n = len(block)
    lens = _row_lengths(block)
    k = int(max_nnz if max_nnz is not None else (lens.max() if n else 1))
    k = max(k, 1)
    rows_out = int(pad_rows_to if pad_rows_to is not None else n)
    indices = np.full((rows_out, k), num_col, dtype=np.int32)
    values = np.zeros((rows_out, k), dtype=np.float32)
    plane = None
    if fields:
        plane = np.zeros((rows_out, k), field_plane_dtype(
            int(block.field.max()) if len(block.field) else 0))
    if n:
        # the real slots of a [n, k] plane, read row-major, are the CSR
        # entries in their own order (of a row that is cut, its first k):
        # each plane is one masked assignment, which holds the interpreter
        # lock for none of its milliseconds
        index, value, field = block.index, block.value, block.field
        if int(lens.max()) > k:
            kept = (np.arange(len(index))
                    - np.repeat(block.offset[:-1], lens)) < k
            index = index[kept]
            value = None if value is None else value[kept]
            field = None if field is None else field[kept]
        slots = np.arange(k) < lens[:, None]
        indices[:n][slots] = index.astype(np.int32)
        values[:n][slots] = 1.0 if value is None else value
        if plane is not None:
            plane[:n][slots] = field
    label = np.zeros(rows_out, np.float32)
    label[:n] = block.label
    weight = np.zeros(rows_out, np.float32)
    weight[:n] = block.weight if block.weight is not None else 1.0
    return EllBatch(indices, values, label, weight, plane)


def block_to_dense(
    block: RowBlock, num_col: int, pad_rows_to: Optional[int] = None,
    copy: bool = True,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """CSR -> padded dense [B, D] (+ label, weight), batch-padded like ELL.

    With ``copy=False`` and dense-in-sparse data whose width equals
    ``num_col`` exactly, ``x`` is returned as a zero-copy reshape view of the
    parser's value array — callers must not mutate it.

    ``x`` is float32, or the block's own int32 / int64 where its values are
    integer cells (a CSV parsed with ``dtype=int32|int64``): ids are never
    rounded through a float on the way.
    """
    n = len(block)
    rows_out = int(pad_rows_to if pad_rows_to is not None else n)
    x = None
    xdt = (block.value.dtype if block.value is not None
           and block.value.dtype.kind == "i" else np.dtype(np.float32))
    if n:
        lens = _row_lengths(block)
        vals = block.value if block.value is not None else np.ones(len(block.index), np.float32)
        k = int(lens[0]) if n else 0
        # fast path for dense-in-sparse data (HIGGS/CSV-shaped): every row has
        # the same k features 0..k-1, so the values are already a dense matrix
        if (
            0 < k <= num_col
            and len(block.index) == n * k
            and bool((lens == k).all())
            and bool((block.index.reshape(n, k) == np.arange(k, dtype=block.index.dtype)).all())
        ):
            if (not copy and k == num_col and rows_out == n
                    and vals.dtype == xdt):
                x = vals.reshape(n, k)
            else:
                x = np.zeros((rows_out, num_col), dtype=xdt)
                x[:n, :k] = vals.reshape(n, k)
        else:
            x = np.zeros((rows_out, num_col), dtype=xdt)
            rows = np.repeat(np.arange(n), lens)
            keep = block.index < num_col
            x[rows[keep], block.index[keep].astype(np.int64)] = vals[keep]
    if x is None:
        x = np.zeros((rows_out, num_col), dtype=xdt)
    label = np.zeros(rows_out, np.float32)
    label[:n] = block.label
    weight = np.zeros(rows_out, np.float32)
    weight[:n] = block.weight if block.weight is not None else 1.0
    return x, label, weight


def _coo_values(block: RowBlock, nnz_out: int,
                unit_values_as_none: bool) -> Optional[np.ndarray]:
    """The float32 values of a COO batch padded with zeros to ``nnz_out``,
    or ``None`` where they are all ones and may be elided."""
    nnz = len(block.index)
    vals: Optional[np.ndarray]
    if block.value is None:
        vals = None if unit_values_as_none else np.ones(nnz_out, np.float32)
    else:
        vals = block.value
        if vals.dtype != np.float32:
            vals = vals.astype(np.float32)
        if unit_values_as_none and nnz and bool((vals == 1.0).all()):
            # binary-feature corpora (CTR one-hot rows, libfm ":1" tokens):
            # the consumer synthesizes ones on device, saving 4 B/nnz of
            # host->HBM traffic — the value array is 1/3 of a COO batch
            vals = None
    if vals is not None and nnz_out > len(vals):
        out = np.zeros(nnz_out, np.float32)
        out[:len(vals)] = vals
        vals = out
    return vals


def block_to_bcoo_host(
    block: RowBlock, num_col: int, pad_rows_to: Optional[int] = None,
    unit_values_as_none: bool = False, pad_nnz_to: Optional[int] = None,
) -> Tuple[np.ndarray, Optional[np.ndarray], np.ndarray, np.ndarray, Tuple[int, int]]:
    """CSR -> host-side COO arrays ``(coords, vals, label, weight, shape)``.

    This is the numpy half of :func:`block_to_bcoo`, split out so a prefetch
    pipeline can run it on a convert thread and keep only the (async)
    device transfer on the consumer thread. Coordinates are int32 whenever
    the shape fits (any realistic corpus: num_col < 2^31): for KDD-shaped
    data the coordinate array dominates transfer bytes, so halving its width
    roughly halves host->HBM traffic for the whole batch. ``pad_rows_to``
    pads the batch dimension (zero-weight empty rows) so every batch shares
    one static shape.

    ``pad_nnz_to`` pads the nnz dimension with OUT-OF-BOUNDS coordinates
    ``(rows_out, num_col)`` — BCOO's canonical padding, masked by every
    sparse op (todense/matvec/matmul drop OOB entries), so the pad values
    are free to be anything and ``unit_values_as_none`` elision composes
    with padding. Quantizing nnz to a bucket multiple keeps the set of
    distinct array shapes small and REPEATING — a fresh shape per batch
    forces a new transfer plan in the runtime and a recompile in any
    downstream jit.
    """
    n = len(block)
    nnz = len(block.index)
    rows_out = int(pad_rows_to if pad_rows_to is not None else n)
    nnz_out = int(pad_nnz_to) if pad_nnz_to is not None and pad_nnz_to > nnz else nnz
    idx_dtype = np.int32 if max(rows_out + 1, num_col + 1) < (1 << 31) else np.int64
    lens = _row_lengths(block)
    coords = np.empty((nnz_out, 2), idx_dtype)
    coords[:nnz, 0] = np.repeat(np.arange(n, dtype=idx_dtype), lens)
    coords[:nnz, 1] = block.index
    coords[nnz:, 0] = rows_out   # OOB pad: masked by all BCOO ops
    coords[nnz:, 1] = num_col
    vals = _coo_values(block, nnz_out, unit_values_as_none)
    label = np.zeros(rows_out, np.float32)
    label[:n] = block.label
    weight = np.zeros(rows_out, np.float32)
    weight[:n] = block.weight if block.weight is not None else 1.0
    return coords, vals, label, weight, (rows_out, num_col)


def parts_to_csr_host(
    parts, num_col: int, pad_rows_to: Optional[int] = None,
    unit_values_as_none: bool = False, pad_nnz_to: Optional[int] = None,
    out: Optional[dict] = None,
) -> Tuple[np.ndarray, np.ndarray, Optional[np.ndarray], np.ndarray,
           np.ndarray, Tuple[int, int]]:
    """CSR row ranges -> the CSR wire ``(cols, row_ptr, vals, label, weight,
    shape)``: :func:`block_to_bcoo_host`'s batch with the row of every slot
    left out. ``parts`` are the RowBlocks (views) that make up the batch in
    order; each is copied once, straight into the wire's arrays, so no
    merged block is built first. ``cols`` is int32 ``[nnz_out]`` padded with
    ``num_col``, ``row_ptr`` int32 ``[rows_out + 1]`` with the pad rows
    pointing at the real nnz, so the consumer's prefix sum
    (``data/device.py:_csr_coords``) gives every pad slot the out-of-bounds
    row ``rows_out``: the pad coordinates are the pair wire's. 8 bytes a slot
    over the link where the pairs are 12 (values 4 more on both), and no
    ``np.repeat`` on the convert thread. ``out`` gives the arrays to fill
    (``cols``, ``row_ptr``, ``vals``, ``label``, ``weight`` at the padded
    sizes: a staging ring's slot); without it they are allocated."""
    n = sum(len(p) for p in parts)
    nnz = sum(len(p.index) for p in parts)
    rows_out = int(pad_rows_to if pad_rows_to is not None else n)
    nnz_out = int(pad_nnz_to) if pad_nnz_to is not None and pad_nnz_to > nnz else nnz
    if out is None:
        out = {"cols": np.empty(nnz_out, np.int32),
               "row_ptr": np.empty(rows_out + 1, np.int32),
               "vals": None,
               "label": np.empty(rows_out, np.float32),
               "weight": np.empty(rows_out, np.float32)}
    cols, row_ptr = out["cols"], out["row_ptr"]
    label, weight = out["label"], out["weight"]
    # all ones and may be elided (binary-feature corpora: the consumer
    # synthesizes ones on device, saving 4 B/nnz of host->HBM traffic)
    elide = unit_values_as_none and (
        all(p.value is None for p in parts)
        or (nnz > 0 and all(p.value is None or bool((p.value == 1.0).all())
                            for p in parts)))
    vals = None
    if not elide:
        vals = out["vals"]
        if vals is None:
            vals = np.empty(nnz_out, np.float32)
    pos = row = 0
    row_ptr[0] = 0
    for p in parts:
        k, m = len(p.index), len(p)
        cols[pos:pos + k] = p.index
        if vals is not None:
            vals[pos:pos + k] = 1.0 if p.value is None else p.value
        np.add(p.offset[1:], pos, out=row_ptr[row + 1:row + m + 1],
               casting="unsafe")
        label[row:row + m] = p.label
        weight[row:row + m] = 1.0 if p.weight is None else p.weight
        pos += k
        row += m
    cols[nnz:] = num_col
    if vals is not None:
        vals[nnz:] = 0.0
    row_ptr[n + 1:] = nnz
    label[n:] = 0.0
    weight[n:] = 0.0
    return cols, row_ptr, vals, label, weight, (rows_out, num_col)


def block_to_bcoo(block: RowBlock, num_col: int):
    """CSR -> jax.experimental.sparse.BCOO (interop layout)."""
    from jax.experimental import sparse as jsparse

    coords, vals, _, _, shape = block_to_bcoo_host(block, num_col)
    return jsparse.BCOO((jnp.asarray(vals), jnp.asarray(coords)), shape=shape)


# ---------------- products ----------------

def ell_matvec(weights: jax.Array, batch: EllBatch) -> jax.Array:
    """Batched sparse dot: out[b] = sum_k w[idx[b,k]] * val[b,k].

    The TPU analog of Row::SDot (data.h:146-161). ``weights`` is [D+1]; the
    final slot is the padding sink (index=num_col) and must be 0 — callers
    keep a D+1 parameter vector and simply never touch the last slot.
    A 2D table [D+1, C] (multinomial per-class weights) broadcasts the
    values over the class dim and returns [B, C].
    """
    gathered = jnp.take(weights, batch.indices, axis=0)  # [B, K] or [B, K, C]
    vals = batch.values if weights.ndim == 1 else batch.values[..., None]
    return jnp.sum(gathered * vals, axis=1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def ell_table_gather(tables: Tuple[jax.Array, ...], indices: jax.Array,
                     deal=None, real=None) -> Tuple[jax.Array, ...]:
    """Rows ``indices`` [...] of every table of ``tables``, which share
    one id space along their first axis (``[W]`` or ``[W, F]``, any F), as
    one ``jnp.take`` a table gives them. A factorization machine passes
    its linear and factor tables ``(w, v)``, a field-aware one its single
    ``[W, m * k]`` table.

    Forward and backward each pick a route from what they observe (a TPU
    backend, float32, a table large against the batch), with no option:

    - the forward (:func:`dmlc_tpu.ops.table_gather.table_rows`) reads the
      rows with XLA's gather or, where that is predicted slower, sorts the
      slots by id and reads them with a one-hot MXU kernel that walks the
      tables block by block; the counter ``table_gather_route`` says
      which, once per traced forward (``predict`` included). The values
      are ``jnp.take``'s for every id of the tables; an id outside them
      reads 0 on the kernel route where ``jnp.take`` gives NaN;
    - the backward hands the cotangents to
      :func:`dmlc_tpu.ops.grad_scatter.dense_table_grad`, which builds the
      dense gradients from the sorted batch rows with the kernel's twin
      (taking the forward's sort where there is one) or with XLA's
      scatter-add; the counter ``grad_scatter_route`` says which, once per
      traced backward.

    On the kernel routes one non-finite table value or cotangent row makes
    a whole chunk of slots or block of table rows non-finite, not one
    (docs/ops.md).

    ``deal`` (:class:`dmlc_tpu.parallel.mesh.RowDeal`) says that the
    tables are *dealt by rows* and the call is made inside
    ``shard_map`` over ``deal.axis`` with this chip's shards and slots:
    every slot's id goes to the chip that owns it, which reads it from its
    shard and sends the row back; the backward sends the cotangent rows
    the same way and each chip adds what it received into the gradient of
    its shard (``collective="owned_rows"``; ops/table_exchange.py). A step
    whose slots do not fit the exchange's buckets all-gathers them
    instead; the rows and the gradient are the same. (Tables laid in
    ranges, :class:`~dmlc_tpu.parallel.mesh.RowRanges`, always do:
    ``table_rows`` says how.)

    Slots whose ``real`` [...] is false (the batch's padding: value 0)
    read zeros on the kernel routes and their cotangent is not looked at:
    with a deal they are not sent, on one chip they take the sorted walk's
    sentinel and the runs of slots that hold nothing else are not permuted
    (``table_rows`` says how an ELL caller lays its slots for that:
    K-major)."""
    return _table_gather_fwd(tables, indices, deal, real)[0]


def _table_gather_fwd(tables, indices, deal, real):
    from dmlc_tpu.ops.table_gather import table_rows

    rows, sorted_slots = table_rows(tables, indices, deal, real)
    # the tables ride along for their shapes only: the backward reads no value
    return rows, (tables, indices, sorted_slots, real)


def _table_gather_bwd(deal, res, g):
    from dmlc_tpu.ops.grad_scatter import dense_table_grad

    tables, indices, sorted_slots, real = res
    grads = dense_table_grad(indices, tuple(g), tables[0].shape[0],
                             sorted_slots=sorted_slots, deal=deal, real=real)
    # (with a deal, sorted_slots is the forward's exchange: its buckets
    # hold what ``real`` said)
    return tuple(d.astype(t.dtype) for d, t in zip(grads, tables)), None, None


ell_table_gather.defvjp(_table_gather_fwd, _table_gather_bwd)


def ell_matmul(weights: jax.Array, batch: EllBatch) -> jax.Array:
    """ELL x dense matrix: [B,K] sparse rows times [D+1, H] -> [B, H]."""
    gathered = jnp.take(weights, batch.indices, axis=0)  # [B, K, H]
    return jnp.einsum("bkh,bk->bh", gathered, batch.values)


def segment_csr_matvec(
    weights: jax.Array,
    index: jax.Array,
    value: jax.Array,
    row_ids: jax.Array,
    num_rows: int,
) -> jax.Array:
    """COO-style matvec via segment_sum, for when nnz varies too much for ELL."""
    prod = jnp.take(weights, index, axis=0) * value
    return jax.ops.segment_sum(prod, row_ids, num_segments=num_rows)
