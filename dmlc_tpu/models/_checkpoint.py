"""Save and resume of a learner's state: what stands behind
``TrainLoopMixin.save`` / ``save_async`` / ``restore`` / ``latest``
(docs/checkpoint.md).

A learner declares its state once (:class:`CheckpointSpec`: static
metadata, the pytree a step reads and writes, the deal of its row tables).
Everything else is here and the same for every learner:

* **A consistent state under donation.** The fused steps update their
  tables in place on donated buffers, so a save cannot read the live
  arrays while steps run behind it. :func:`begin_save` dispatches one
  device program (``ckpt_snapshot``) that copies every leaf into chunks
  of whole rows, in stream order after the last dispatched step and
  before the next; the copy is the state after exactly that many steps,
  whatever is dispatched afterwards. The dispatching thread is held for
  the dispatch alone (span ``ckpt_snapshot``).
* **The drain.** A saver thread brings the chunks to the host a few at a
  time (plain device-to-host transfers: no program queues behind the
  steps), checksums and writes each through
  :class:`dmlc_tpu.io.checkpoint.CheckpointWriter`, frees it on the
  device, then syncs and publishes through the artifact store's
  ``checkpoint`` tier and bounds the tier by count. Host memory is a few
  chunks; the device copy shrinks as the drain goes.
* **Where no copy fits** (the device's own statistics say so),
  ``save_async`` refuses with the sizes named and ``save`` reads the live
  arrays chunk by chunk: its caller is not stepping, so they stand still.
* **Restore under any deal.** A file addresses rows by global id; every
  device of the target learner takes the rows it holds from whichever
  files hold them, block by block, into the learner's own buffers.
"""

from __future__ import annotations

import collections
import functools
import json
import os
import threading
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from dmlc_tpu.io import checkpoint as _ck
from dmlc_tpu.utils import telemetry as _telemetry
from dmlc_tpu.utils.check import DMLCError, check
from dmlc_tpu.utils.timer import get_time

# A chunk of rows is the largest power of two of rows under this many
# bytes. Small on purpose: a transfer to the host ends with the runtime
# rewriting the chunk from the device's tiles into a numpy buffer, and a
# step's completion that falls behind that is seen late by as long as it
# takes. At 64 MiB a save put single gaps of 90-137 ms between the
# completions of 71 ms steps (the device never idle); at 8 MiB none
# (PERF.md section 6, PR 41). Restoring reads small chunks faster too.
CHUNK_BYTES = 8 << 20
IN_FLIGHT = 3            # chunks on their way to the host at once
_CHUNK_CACHE = 8         # decoded source chunks a restore keeps


class CheckpointSpec(NamedTuple):
    """A learner's one declaration of what a checkpoint holds.

    ``meta``: static, JSON-able: class, widths, optimiser and its
    constants. A restore refuses a file whose ``meta`` differs.
    ``tree``: the pytree of arrays a step reads and writes.
    ``deal``: the :class:`~dmlc_tpu.parallel.mesh.RowDeal` of its row
    tables (leaves of ``deal.padded_rows`` rows), or ``None``.
    ``layout_bound``: names of leaves shaped by the layout itself (a
    dealt learner's per-chip books): restored only under the same shape.
    """
    meta: dict
    tree: Any
    deal: Any = None
    layout_bound: Tuple[str, ...] = ()


def _key(k) -> str:
    for attr in ("name", "key", "idx"):
        if hasattr(k, attr):
            return str(getattr(k, attr))
    return str(k)


def named_leaves(tree) -> Tuple[List[str], list, Any]:
    """``(names, leaves, treedef)``: every leaf under the dotted path of
    its keys (``params.w``, ``opt_state.0.mu.v``)."""
    import jax

    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    return ([".".join(_key(k) for k in path) for path, _ in flat],
            [leaf for _, leaf in flat], treedef)


def chunk_rows_of(shape, dtype, chunk_bytes: Optional[int] = None) -> int:
    """Rows of a chunk of such a leaf: the largest power of two under
    ``chunk_bytes`` (a file's own, when one is read; else
    :data:`CHUNK_BYTES`)."""
    if chunk_bytes is None:
        chunk_bytes = CHUNK_BYTES
    row_bytes = int(np.prod(shape[1:], dtype=np.int64)) * np.dtype(
        dtype).itemsize
    rows = 1
    while rows * 2 * row_bytes <= chunk_bytes:
        rows *= 2
    return rows


def _dealt(leaf, deal) -> bool:
    return (deal is not None and leaf.ndim >= 1
            and leaf.shape[0] == deal.padded_rows)


# ---------------------------------------------------------------------------
# the save
# ---------------------------------------------------------------------------

class _Plan:
    """How one learner's tree is cut: per leaf the local rows a file
    holds and their chunk edges; the compiled snapshot program."""

    def __init__(self, names, leaves, deal, mesh):
        self.names, self.deal, self.mesh = list(names), deal, mesh
        self.shards = 1 if deal is None else deal.shards
        self.dealt = [_dealt(x, deal) for x in leaves]
        self.avals = [(tuple(x.shape), np.dtype(x.dtype)) for x in leaves]
        self.edges = []
        for (shape, dtype), dealt in zip(self.avals, self.dealt):
            rows = deal.local_rows if dealt else shape[0] if shape else 1
            step = chunk_rows_of(shape, dtype)
            self.edges.append(list(range(0, rows, step)) + [rows]
                              if shape else [0, 1])
        self.chunk_bytes = CHUNK_BYTES
        self._compiled = self._bytes = None

    def _cut(self, leaves):
        """Every leaf as its chunks: the traced body of the snapshot."""
        import jax
        from jax.sharding import PartitionSpec as P

        out = []
        for leaf, dealt, edges in zip(leaves, self.dealt, self.edges):
            pairs = list(zip(edges, edges[1:]))
            if not leaf.ndim:
                out.append((leaf + 0,))
            elif dealt and self.mesh is not None:
                spec = P(self.deal.axis, *([None] * (leaf.ndim - 1)))
                out.append(jax.shard_map(
                    lambda shard, pairs=pairs: tuple(
                        shard[a:b] for a, b in pairs),
                    mesh=self.mesh, in_specs=(spec,),
                    out_specs=P(self.deal.axis), check_vma=False)(leaf))
            else:
                out.append(tuple(leaf[a:b] for a, b in pairs))
        return tuple(out)

    def snapshot_program(self, leaves):
        """The compiled ``ckpt_snapshot`` for these leaves (compiled on
        the first save; its cost counted there)."""
        if self._compiled is None:
            import jax

            def ckpt_snapshot(*leaves):
                with jax.named_scope("ckpt_snapshot"):
                    return self._cut(leaves)

            options = {}
            if self.mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec as P

                rep = NamedSharding(self.mesh, P())
                rows = NamedSharding(self.mesh, P(self.deal.axis)) \
                    if self.deal is not None else rep
                options["out_shardings"] = tuple(
                    tuple((rows if dealt else rep) for _ in edges[1:])
                    for dealt, edges in zip(self.dealt, self.edges))
            _telemetry.arm_compile_counters()
            self._compiled = jax.jit(ckpt_snapshot, **options).lower(
                *leaves).compile()
        return self._compiled

    def snapshot_bytes(self, leaves) -> int:
        """Bytes the device copy takes on one device, as laid out."""
        if self._bytes is None:
            mem = self.snapshot_program(leaves).memory_analysis()
            self._bytes = int(mem.output_size_in_bytes
                              + mem.temp_size_in_bytes)
        return self._bytes


_PLANS: Dict[tuple, _Plan] = {}   # a process compiles a snapshot once


def _plan_of(names, leaves, deal, mesh) -> _Plan:
    """The plan, and with it the compiled snapshot, of every learner whose
    state is named, shaped and placed like this one's: a learner built
    again (a resumed job's) does not compile again."""
    key = (tuple(names),
           tuple((x.shape, str(x.dtype), x.sharding) for x in leaves),
           deal, CHUNK_BYTES)
    if key not in _PLANS:
        _PLANS[key] = _Plan(names, leaves, deal, mesh)
    return _PLANS[key]


def _store_of(uri: str):
    """The artifact store of the checkpoint directory ``uri``."""
    from dmlc_tpu.store import store_for

    return store_for(os.path.join(uri, "x"))


@functools.lru_cache(maxsize=None)
def _row_updater():
    """``(buf, rows, at) -> buf`` with ``rows`` written at row ``at``, in
    place on the donated ``buf``: one jitted function a process."""
    import jax

    return jax.jit(
        lambda buf, rows, at: jax.lax.dynamic_update_slice(
            buf, rows, (at,) + (0,) * (buf.ndim - 1)), donate_argnums=0)


class CheckpointRefused(DMLCError):
    """``save_async`` found no room on the device for a copy of the
    state."""


def _room(leaves) -> Optional[int]:
    """Bytes a device can give a copy of the state: its limit, less what
    it holds now, less a quarter of that again for the temporaries of the
    steps that run beside the drain; the least over the devices, ``None``
    where the backend keeps no statistics."""
    room = None
    for dev in leaves[0].sharding.device_set:
        stats = dev.memory_stats() or {}
        if "bytes_limit" not in stats:
            return None
        held = int(stats.get("bytes_in_use", 0))
        free = int(stats["bytes_limit"]) - held - held // 4
        room = free if room is None else min(room, free)
    return room


class SaveHandle:
    """One save in flight. :meth:`wait` returns when the checkpoint is
    published and durable (its files' paths), or raises what the saver
    thread met."""

    def __init__(self, step: int):
        self.step = int(step)
        self.paths: List[str] = []
        self.nbytes = 0
        self.seconds: Dict[str, float] = {}
        self._done = threading.Event()
        self._error: Optional[BaseException] = None

    def done(self) -> bool:
        return self._done.is_set()

    def wait(self, timeout: Optional[float] = None) -> List[str]:
        if not self._done.wait(timeout):
            raise TimeoutError(f"checkpoint of step {self.step} still in "
                               f"flight after {timeout} s")
        if self._error is not None:
            raise self._error
        return list(self.paths)


class _Books:
    """A learner's checkpoint bookkeeping (``checkpoint_stats``)."""

    def __init__(self):
        self.plan: Optional[_Plan] = None
        self.inflight: Optional[SaveHandle] = None
        self.last_save: Optional[dict] = None
        self.last_restore: Optional[dict] = None


def books_of(learner) -> _Books:
    books = getattr(learner, "_ckpt_books", None)
    if books is None:
        books = learner._ckpt_books = _Books()
    return books


def _count(result: str) -> None:
    _telemetry.REGISTRY.counter(_telemetry.CKPT_SAVES_METRIC,
                                result=result).inc(1)


def _in_flight(delta: int) -> None:
    # the dispatching thread adds, the saver thread takes away
    _telemetry.REGISTRY.gauge(_telemetry.CKPT_IN_FLIGHT_METRIC).add(delta)


def _header(spec: CheckpointSpec, plan: _Plan, step: int, iterator,
            shard: int) -> dict:
    deal = spec.deal
    tables = {}
    for name, (shape, dtype), dealt in zip(plan.names, plan.avals,
                                           plan.dealt):
        if dealt:
            first, stride, rows = deal.owned(shard)
            tables[name] = {"dtype": dtype.name, "first_id": first,
                            "id_stride": stride, "global_rows": deal.num_rows,
                            "shape": [rows] + list(shape[1:])}
        elif shard == 0:
            tables[name] = {"dtype": dtype.name, "first_id": 0,
                            "id_stride": 1,
                            "global_rows": shape[0] if shape else None,
                            "shape": list(shape)}
        else:
            continue
        if name in spec.layout_bound:
            tables[name]["layout_bound"] = True
    return {"format": _ck.CHECKPOINT_VERSION, "learner": spec.meta,
            "step": int(step), "iterator": iterator,
            "deal": None if deal is None else deal.describe(),
            "shard": shard, "shards": plan.shards,
            "chunk_bytes": plan.chunk_bytes, "tables": tables}


def begin_save(learner, uri: str, step: int, device_iter=None,
               keep_last: int = 2, snapshot: bool = True) -> SaveHandle:
    """Start a save of ``learner``'s state after ``step`` steps into the
    directory ``uri`` and return its handle. Called from the thread that
    dispatches steps, between two steps. ``snapshot=False`` is
    ``TrainLoopMixin.save``'s alone, where no copy fits: no device copy;
    the live arrays are read chunk by chunk, and its caller does not step
    until it returns."""
    check(keep_last >= 1, "checkpoint: keep_last must be >= 1")
    t_call = get_time()
    books = books_of(learner)
    if books.inflight is not None and not books.inflight.done():
        # durability first: the earlier save is finished, never dropped
        t0 = get_time()
        with _telemetry.span("ckpt_wait_previous"):
            books.inflight._done.wait()
        _telemetry.REGISTRY.counter(
            _telemetry.CKPT_WAIT_PREVIOUS_METRIC).inc(get_time() - t0)
    spec = learner._checkpoint_spec()
    names, leaves, _ = named_leaves(spec.tree)
    plan = books.plan = _plan_of(names, leaves, spec.deal,
                                 getattr(learner, "mesh", None))
    iterator = device_iter.state_dict() if device_iter is not None else None
    handle = SaveHandle(step)
    with _telemetry.span("ckpt_snapshot", step=int(step)) as sp:
        if snapshot:
            program = plan.snapshot_program(leaves)
            need = plan.snapshot_bytes(leaves)
            room = _room(leaves)
            if room is not None and need > room:
                _count("refused")
                raise CheckpointRefused(
                    f"checkpoint: the device copy of the state takes "
                    f"{need:,} bytes a device and {room:,} are free "
                    f"beside what the device holds and the steps' "
                    f"reserve; save() writes the live arrays chunk by "
                    f"chunk instead, with the steps stopped")
            chunks = program(*leaves)
        else:
            chunks = None
    handle.seconds["snapshot"] = sp.dt
    os.makedirs(uri, exist_ok=True)
    books.inflight = handle
    _in_flight(+1)
    source = chunks if chunks is not None else _LiveChunks(plan, leaves)
    del chunks
    thread = threading.Thread(
        target=_telemetry.scoped_target(_drain), name="dmlc-ckpt-saver",
        # the saver holds the copy alone: not the live tree, which a
        # learner dropped meanwhile must be free to give back
        args=(handle, spec._replace(tree=None), plan, source, uri, iterator,
              keep_last, books, t_call),
        daemon=True)
    thread.start()
    return handle


class _LiveChunks:
    """The chunks of the live leaves, cut one at a time as the drain asks
    (``save`` where no device copy fits)."""

    def __init__(self, plan: _Plan, leaves):
        self.plan, self.leaves = plan, leaves

    def __getitem__(self, i):
        import jax

        plan, leaf = self.plan, self.leaves[i]
        if not leaf.ndim:
            return [leaf]
        if not plan.dealt[i] or plan.mesh is None:
            return _Lazy(lambda a, b: leaf[a:b], plan.edges[i])
        return _Lazy(lambda a, b: jax.make_array_from_single_device_arrays(
            (plan.shards * (b - a),) + leaf.shape[1:], leaf.sharding,
            [s.data[a:b] for s in leaf.addressable_shards]), plan.edges[i])


class _Lazy:
    def __init__(self, cut, edges):
        self.cut, self.edges = cut, edges

    def __len__(self):
        return len(self.edges) - 1

    def __getitem__(self, k):
        return self.cut(self.edges[k], self.edges[k + 1])


def _shard_data(chunk, shard: int, dealt: bool):
    """The single-device array of ``chunk`` that file ``shard`` holds."""
    parts = chunk.addressable_shards
    if not dealt or len(parts) == 1:
        return parts[0].data
    rows = chunk.shape[0] // len(chunk.sharding.device_set)
    for part in parts:
        if (part.index[0].start or 0) == shard * rows:
            return part.data
    raise DMLCError(f"checkpoint: shard {shard} is not on this process")


def _drain(handle: SaveHandle, spec: CheckpointSpec, plan: _Plan, source,
           uri: str, iterator, keep_last: int, books: _Books,
           t_call: float) -> None:
    # drain: the saver's waits for chunks asked for up to IN_FLIGHT
    # earlier; landed: from the first transfer asked for to the last
    # chunk on the host, the writes between them included (a file's
    # seconds summed over the shards' files)
    seconds = {"drain": 0.0, "write": 0.0, "landed": 0.0}
    writer = None
    try:
        for shard in range(plan.shards):
            path = os.path.join(uri, _ck.checkpoint_name(
                handle.step, shard, plan.shards))
            writer = _ck.CheckpointWriter(
                path, _header(spec, plan, handle.step, iterator, shard))
            todo = []   # (leaf, chunk of it, its first row, rows kept)
            for i, name in enumerate(plan.names):
                if name not in writer.tables:
                    continue
                keep = writer.tables[name]["shape"][0] \
                    if plan.avals[i][0] else 1
                edges = plan.edges[i]
                todo += [(i, k, edges[k], min(edges[k + 1], keep) - edges[k])
                         for k in range(len(edges) - 1) if edges[k] < keep]
            ahead: collections.deque = collections.deque()
            t_first = get_time()
            for n, (i, k, row0, rows) in enumerate(todo):
                while len(ahead) < IN_FLIGHT and n + len(ahead) < len(todo):
                    j, kk = todo[n + len(ahead)][:2]
                    ahead.append(_shard_data(source[j][kk], shard,
                                             plan.dealt[j]))
                    ahead[-1].copy_to_host_async()
                name, data = plan.names[i], ahead.popleft()
                with _telemetry.span("ckpt_drain", chunk=n) as sp:
                    host = np.asarray(data)
                seconds["drain"] += sp.dt
                t_landed = get_time()
                data.delete()   # the device copy shrinks as we go
                with _telemetry.span("ckpt_write", chunk=n) as sp:
                    writer.add_chunk(name, row0,
                                     host[:rows] if host.ndim else host)
                seconds["write"] += sp.dt
                del host, data
            if todo:
                seconds["landed"] += t_landed - t_first
            t0 = get_time()
            writer.finish()
            seconds["publish"] = seconds.get("publish", 0.0) + get_time() - t0
            handle.paths.append(path)
            handle.nbytes += writer.nbytes
            writer = None
        _store_of(uri).retain("checkpoint", keep_last, group=_group_of)
        seconds["published"] = get_time() - t_call   # from the call
        handle.seconds.update(seconds)
        _telemetry.REGISTRY.counter(_telemetry.CKPT_BYTES_METRIC).inc(
            handle.nbytes)
        _count("ok")
        books.last_save = {"step": handle.step, "bytes": handle.nbytes,
                           "files": len(handle.paths),
                           **{k + "_s": v for k, v in handle.seconds.items()}}
    except BaseException as exc:  # noqa: BLE001 - handed to wait()
        if writer is not None:
            writer.abort()
        handle._error = exc
        _count("failed")
    finally:
        _in_flight(-1)
        handle._done.set()


def _group_of(name: str):
    parsed = _ck.parse_checkpoint_name(name)
    return name if parsed is None else (parsed[0], parsed[2])


# ---------------------------------------------------------------------------
# latest / restore
# ---------------------------------------------------------------------------

def latest(uri: str) -> Optional[dict]:
    """The newest published checkpoint under the directory ``uri`` whose
    files are all there: ``{"step", "paths"}``, else ``None``. Newest by
    publish order in the store's manifest, not by step number."""
    if not os.path.isdir(uri):
        return None
    groups: Dict[tuple, Dict[int, str]] = {}
    order: Dict[tuple, int] = {}
    for n, entry in enumerate(_store_of(uri).entries()):
        parsed = _ck.parse_checkpoint_name(entry["path"])
        if entry["tier"] != "checkpoint" or entry["evicted"] or not parsed:
            continue
        step, shard, shards = parsed
        groups.setdefault((step, shards), {})[shard] = os.path.join(
            uri, entry["path"])
        order[(step, shards)] = n
    whole = [g for g in groups if len(groups[g]) == g[1]]
    if not whole:
        return None
    best = max(whole, key=order.get)
    return {"step": best[0],
            "paths": [groups[best][c] for c in range(best[1])]}


class _Source:
    """The files of one checkpoint, rows of a table by global id."""

    def __init__(self, paths: List[str]):
        self.readers = [_ck.CheckpointReader(p) for p in paths]
        self.header = self.readers[0].header
        self._cache: Dict[tuple, np.ndarray] = {}
        self.seconds = {"read": 0.0, "verify": 0.0}
        self.nbytes = 0

    def close(self) -> None:
        self._cache.clear()     # views of the mapped files
        for r in self.readers:
            r.close()

    def tables(self) -> Dict[str, dict]:
        out: Dict[str, dict] = {}
        for r in self.readers:
            for name, t in r.tables.items():
                out.setdefault(name, t)
        return out

    def _chunk(self, f: int, k: int) -> np.ndarray:
        got = self._cache.pop((f, k), None)
        if got is None:
            reader = self.readers[f]
            with _telemetry.span("ckpt_restore_read", chunk=k) as sp:
                data = reader.chunk_bytes(k)
            self.seconds["read"] += sp.dt
            with _telemetry.span("ckpt_restore_verify", chunk=k) as sp:
                reader.verify_chunk(k, data)
            self.seconds["verify"] += sp.dt
            got = reader.decode(k, data)
            self.nbytes += got.nbytes
            while len(self._cache) >= _CHUNK_CACHE:
                self._cache.pop(next(iter(self._cache)))
        self._cache[(f, k)] = got
        return got

    def scalar(self, name: str) -> np.ndarray:
        for f, r in enumerate(self.readers):
            if name in r.tables:
                return self._chunk(f, r.chunks_of(name)[0])
        raise KeyError(name)

    def rows(self, name: str, ids: np.ndarray, shape_tail, dtype):
        """Rows of table ``name`` at global ids ``ids`` (ascending);
        zeros where an id is past the table's rows."""
        out = found = None
        for f, r in enumerate(self.readers):
            t = r.tables.get(name)
            if t is None:
                continue
            rel = ids - t["first_id"]
            local = rel // t["id_stride"]
            here = (rel % t["id_stride"] == 0) & (local >= 0) & (
                local < t["shape"][0])
            if not here.any():
                continue
            ks = r.chunks_of(name)
            row0 = np.asarray([r.chunks[k]["row0"] for k in ks])
            which = np.searchsorted(row0, local[here], side="right") - 1
            if out is None and here.all() and which[0] == which[-1]:
                chunk = self._chunk(f, ks[which[0]])
                first = local[0] - row0[which[0]]
                if len(chunk) == len(ids) and first == 0 \
                        and local[-1] - local[0] == len(ids) - 1:
                    return chunk, here     # the block is this chunk, whole
            if out is None:
                out = np.zeros((len(ids),) + tuple(shape_tail), dtype)
                found = np.zeros(len(ids), bool)
            at = np.flatnonzero(here)
            for j in np.unique(which):
                sel = which == j
                chunk = self._chunk(f, ks[j])
                want, pos = local[here][sel] - row0[j], at[sel]
                if want[-1] - want[0] == pos[-1] - pos[0] == len(want) - 1:
                    out[pos[0]:pos[-1] + 1] = chunk[want[0]:want[-1] + 1]
                else:
                    out[pos] = chunk[want]
            found |= here
        if out is None:
            out = np.zeros((len(ids),) + tuple(shape_tail), dtype)
            found = np.zeros(len(ids), bool)
        return out, found


def _parts(leaf, dealt: bool, deal) -> List[tuple]:
    """``(device shard, first id, id stride)`` of every addressable shard
    of ``leaf``: which global row each of its rows stands for."""
    out = []
    for part in leaf.addressable_shards:
        check(all(s == slice(None) or (s.start in (None, 0)
                                       and s.stop in (None, n))
                  for s, n in zip(part.index[1:], leaf.shape[1:])),
              "checkpoint: a leaf sharded along another axis than its "
              "rows is not restored")
        start = (part.index[0].start or 0) if leaf.ndim else 0
        if dealt:
            # (chip c holds the dealt array's rows from c * local_rows)
            out.append((part,) + deal.owned(start // deal.local_rows)[:2])
        else:
            out.append((part, start, 1))
    return out


def restore(learner, uri: str, device_iter=None) -> dict:
    """Bring ``learner`` (built with the arguments of the learner that
    saved) to the state of the newest checkpoint under the directory
    ``uri``, or of the checkpoint one of whose files ``uri`` names; with
    ``device_iter``, bring it to the saved position too. Returns
    ``{"step", "iterator", "paths"}``. A file that is truncated, or a
    chunk whose CRC fails, is refused by name; after a refusal the
    learner's buffers are spent and it has to be built again."""
    import jax
    import jax.numpy as jnp

    t_start = get_time()
    if os.path.isdir(uri):
        found = latest(uri)
        check(found is not None,
              f"checkpoint: nothing published under {uri}")
        paths = found["paths"]
    else:
        parsed = _ck.parse_checkpoint_name(uri)
        check(parsed is not None, f"checkpoint: {uri} is neither a "
              "directory nor a checkpoint's file")
        paths = [os.path.join(os.path.dirname(uri), _ck.checkpoint_name(
            parsed[0], c, parsed[2])) for c in range(parsed[2])]
    spec = learner._checkpoint_spec()
    names, leaves, treedef = named_leaves(spec.tree)
    source = _Source(paths)
    put_s = 0.0
    try:
        header = source.header
        mine = json.loads(json.dumps(spec.meta))
        if header["learner"] != mine:
            differ = sorted(k for k in set(header["learner"]) | set(mine)
                            if header["learner"].get(k) != mine.get(k))
            raise DMLCError(
                f"checkpoint {paths[0]}: written by another learner; "
                + "; ".join(f"{k}: file {header['learner'].get(k)!r}, "
                            f"learner {mine.get(k)!r}" for k in differ))
        tables = source.tables()
        extra = [n for n, t in tables.items()
                 if n not in names and not t.get("layout_bound")]
        check(not extra, f"checkpoint {paths[0]}: holds {extra}, which "
              "this learner has not")
        update, new_leaves = _row_updater(), []
        for name, leaf in zip(names, leaves):
            t = tables.get(name)
            bound = name in spec.layout_bound
            dealt = _dealt(leaf, spec.deal)
            rows_now = (spec.deal.num_rows if dealt
                        else leaf.shape[0] if leaf.ndim else None)
            if t is None or (bound and (
                    t["global_rows"] != rows_now
                    or list(t["shape"][1:]) != list(leaf.shape[1:]))):
                check(bound, f"checkpoint {paths[0]}: no table {name}")
                new_leaves.append(leaf)   # the layout's own: as built
                continue
            check(t["dtype"] == np.dtype(leaf.dtype).name
                  and t["global_rows"] == rows_now
                  and list(t["shape"][1:]) == list(leaf.shape[1:]),
                  f"checkpoint {paths[0]}: table {name} is "
                  f"{t['dtype']} {t['global_rows']} x {t['shape'][1:]}, "
                  f"the learner's {leaf.dtype} {rows_now} x "
                  f"{list(leaf.shape[1:])}")
            if not leaf.ndim:
                with _telemetry.span("ckpt_restore_put") as sp:
                    new_leaves.append(jax.device_put(
                        source.scalar(name), leaf.sharding))
                put_s += sp.dt
                continue
            parts = _parts(leaf, dealt, spec.deal)
            # the learner's own buffers take the rows, donated block by
            # block: no second table stands on the device
            one = len(leaf.sharding.device_set) == 1
            bufs = [leaf] if one else [part.data for part, _, _ in parts]
            local = bufs[0].shape[0]
            block = chunk_rows_of(leaf.shape, leaf.dtype,
                                  header["chunk_bytes"])
            for j0 in range(0, local, block):
                j1 = min(local, j0 + block)
                for p, (part, first, stride) in enumerate(parts):
                    ids = first + stride * np.arange(j0, j1, dtype=np.int64)
                    rows, found = source.rows(
                        name, ids, bufs[p].shape[1:], bufs[p].dtype)
                    missing = ~found & (ids < t["global_rows"])
                    check(not missing.any(),
                          f"checkpoint {paths[0]}: rows of {name} from id "
                          f"{ids[missing][:1]} are in none of its files")
                    with _telemetry.span("ckpt_restore_put") as sp:
                        rows = jax.device_put(rows, part.device)
                        bufs[p] = update(bufs[p], rows, jnp.int32(j0))
                    put_s += sp.dt
            new_leaves.append(
                bufs[0] if one else
                jax.make_array_from_single_device_arrays(
                    leaf.shape, leaf.sharding, bufs))
        jax.block_until_ready(new_leaves)
        learner._checkpoint_adopt(
            jax.tree_util.tree_unflatten(treedef, new_leaves))
        if device_iter is not None and header["iterator"] is not None:
            device_iter.load_state(header["iterator"])
    finally:
        source.close()
    books_of(learner).last_restore = {
        "step": header["step"], "bytes": source.nbytes,
        "files": len(paths), "read_s": source.seconds["read"],
        "verify_s": source.seconds["verify"], "put_s": put_s,
        "total_s": get_time() - t_start}
    return {"step": header["step"], "iterator": header["iterator"],
            "paths": paths}


def stats(learner) -> dict:
    """What ``learner.checkpoint_stats()`` gives: the process's counters
    and this learner's last save and restore by phase."""
    books = books_of(learner)
    out = _telemetry.checkpoint_counters()
    out["last_save"] = books.last_save
    out["last_restore"] = books.last_restore
    return out
