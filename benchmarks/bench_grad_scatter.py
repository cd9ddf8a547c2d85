"""Time the pieces of ops/grad_scatter.py on the chip, alone, at the KDD12
factorization machine's shapes: XLA's scatter-add, the ways to sort the
payload, the kernel over a grid of (block ids, chunk slots), and steps A+B
end to end. The routing constants in ops/grad_scatter.py come from here
(PERF.md §6, PR 25):

    chiprun -- python3 benchmarks/bench_grad_scatter.py [--ffm] [--sorts] [--grid] [--variadic]
    chiprun -- python3 benchmarks/bench_grad_scatter.py --gather [--ffm]
    chiprun -- python3 benchmarks/bench_grad_scatter.py --fused [--ffm] [--ladders]

``--ffm`` takes the kdd12_ffm shape in place of the FM's (one table of
13,671,614 rows and 44 columns, PR 26), ``--sorts`` adds the ways to sort
a payload, ``--grid`` the wide tile grid, ``--variadic`` the ten-operand
sort (99 s to compile). (The four-chip legs went with the replicated
tables in PR 54: a chip's share of the row-laid step is
``bench_laid_chip.py``'s.) ``--gather`` runs only the forward's leg
(ops/table_gather.py, PR 29): XLA's ``take`` a table, the two sorts, the
``table_gather`` kernel with and without slots (beside the kernel's time
its ``tile_products`` and the ``tile_products_whole_block`` it made until
PR 43, from ``table_gather_tile_counts``), the way back to batch order,
and the whole forward on each route, which must agree value for value
(``_KERNEL_NS_*`` and ``_XLA_NS_PER_INDEX`` there); without ``--ffm`` also
the kernel's pieces on a batch of kddb_fm's ragged slots. ``--fused`` runs
only the leg of the kernel's epilogue (PR 31: the FM's Adam; with ``--ffm``
libffm's AdaGrad on the 44-column table, PR 34): the two passes it
replaces (the dense gradient with its sort and permute, then optax's
sweep over it), the kernel with the epilogue alone with the slots and with
none at 1, 2, 4 and 8 blocks a grid step (1, 2 and 4 at 44 columns), and
the whole update, checked against the two passes on the rows the batch
touched and on rows it did not; since PR 46 beside the kernel's time its
``tile_products`` and the ``tile_products_whole_block`` it made until then
(``grad_scatter_tile_counts``), the kernel with every pair contracted over
its whole block (the same bits: ``fused_kernel_bits``), with ``--ladders``
the kernel on shorter ladders, and without ``--ffm`` the Adam kernel's
pieces on a batch of kddb_fm's ragged slots.

One JSON line per timing (median ms of five warm calls); needs a TPU.
"""

from __future__ import annotations

import functools
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from cellbench.generators import fields_zipf_libfm as gen
from dmlc_tpu.ops import grad_scatter as gs
from dmlc_tpu.ops import sorted_walk as sw
from dmlc_tpu.ops import table_gather as tg
from dmlc_tpu.utils import telemetry

FFM = "--ffm" in sys.argv
# rows of the tables; F: an FM's factor columns beside its linear column,
# or the columns of the field-aware FM's one table
W1, F, B, K = (13_671_614, 44, 65_536, 16) if FFM else \
    (54_686_453, 8, 65_536, 16)


def cotangents(g_w, g_v):
    """The op's cotangents: ``(g_w, g_v)`` for the FM's two tables, the
    one ``[N, 44]`` for the field-aware FM's."""
    return (g_v,) if FFM else (g_w, g_v)


def columns(g_w, g_v):
    return g_v.T if FFM else jnp.concatenate([g_v.T, g_w[None]])


TRAILING = ((F,),) if FFM else ((), (F,))


def batch_ids(seed: int, rows: int) -> np.ndarray:
    """[rows, K] ids as a batch of the kdd12_fm cells holds them: one id
    in each of 11 fields, the rest on the sink row."""
    params = {"num_features": W1 - 1, "fields": 11, "zipf_s": 1.1,
              "label_noise": 1.0}
    ids, _ = gen.draw_rows(params, np.random.SeedSequence(seed), rows)
    out = np.full((rows, K), W1 - 1, np.int32)
    out[:, :ids.shape[1]] = ids
    return out


def timed(name: str, fn, *args, reps: int = 5, **note):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    first = time.perf_counter() - t0
    ms = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ms.append((time.perf_counter() - t0) * 1e3)
    print(json.dumps({"piece": name, "ms": round(statistics.median(ms), 3),
                      "min_ms": round(min(ms), 3),
                      "first_s": round(first, 2), **note}), flush=True)
    return out


ADAM = gs.AdamEpilogue(0.05)
ADAGRAD = gs.AdaGradEpilogue(0.2)
EPILOGUE = ADAGRAD if FFM else ADAM


def adam_state(sharding=None, rows: int = W1):
    """``((w, m, n), (v, m, n))`` at the FM's shape (or of ``rows`` rows):
    parameters and moments as a few steps leave them (the second moment
    positive). With ``--ffm`` ``((W, G),)``: the table as libffm starts it
    and accumulators a few steps above 1."""
    def make():
        if FFM:
            k_w, k_g = jax.random.split(jax.random.key(3))
            return ((0.5 * jax.random.uniform(k_w, (rows, F), jnp.float32),
                     1.0 + jnp.square(jax.random.normal(
                         k_g, (rows, F), jnp.float32))),)
        keys = jax.random.split(jax.random.key(3), 6)
        draw = lambda k, shape, scale: scale * jax.random.normal(  # noqa: E731
            k, shape, jnp.float32)
        return tuple(
            (draw(k[0], shape, 0.01), draw(k[1], shape, 1e-3),
             jnp.square(draw(k[2], shape, 1e-3)))
            for k, shape in ((keys[:3], (rows,)), (keys[3:], (rows, F))))
    return jax.block_until_ready(jax.jit(
        make, out_shardings=sharding and ((sharding,) * 3,) * 2)())


def two_passes(state, count, ids, g_w, g_v):
    """What the fused update replaces: the dense gradient written by the
    kernel, then ``optax.adam`` (``--ffm``: libffm's AdaGrad, as
    ``FFMLearner`` chains it) over it."""
    import optax

    grads = gs.dense_table_grad(ids, cotangents(g_w, g_v), W1)
    if FFM:
        (w, acc), = state
        opt = optax.chain(
            optax.scale_by_rss(initial_accumulator_value=1.0, eps=0.0),
            optax.scale(-ADAGRAD.learning_rate))
        updates, (rss, _) = opt.update(
            grads, (optax.ScaleByRssState((acc,)), optax.ScaleState()), (w,))
        return (optax.apply_updates((w,), updates)
                + rss.sum_of_squares,)
    params, mu, nu = zip(*state)
    opt = optax.adam(ADAM.learning_rate)
    updates, (adam, _) = opt.update(
        grads, (optax.ScaleByAdamState(count, mu, nu), optax.EmptyState()),
        params)
    return tuple(zip(optax.apply_updates(params, updates), adam.mu, adam.nu))


def fused(state, count, ids, g_w, g_v):
    return gs.fused_table_update(
        ids, cotangents(g_w, g_v), state,
        None if FFM else ADAM.bias(count + 1), EPILOGUE)


def timed_in_place(name: str, fn, state, *args, reps: int = 5, **note):
    """As :func:`timed` for ``fn(state, *args) -> state`` compiled with the
    state donated: every call takes the last one's result."""
    t0 = time.perf_counter()
    state = jax.block_until_ready(fn(state, *args))
    first = time.perf_counter() - t0
    ms = []
    for _ in range(reps):
        t0 = time.perf_counter()
        state = jax.block_until_ready(fn(state, *args))
        ms.append((time.perf_counter() - t0) * 1e3)
    print(json.dumps({"piece": name, "ms": round(statistics.median(ms), 3),
                      "min_ms": round(min(ms), 3),
                      "first_s": round(first, 2), **note}), flush=True)
    return state


def sampled(state, at):
    return [np.asarray(x[at]) for table in state for x in table]


def update_check(name, got, want, touched: int, **note) -> None:
    """``got`` / ``want``: :func:`sampled` rows of both updates, the first
    ``touched`` of them rows the batch named. The widest gap of a leaf
    over its widest element."""
    def gaps(part):
        return [float(np.abs(a[part] - b[part]).max()
                      / max(np.abs(b[part]).max(), 1e-30))
                for a, b in zip(got, want)]
    print(json.dumps({
        "piece": name, "leaves": "W G" if FFM else "w m_w n_w v m_v n_v",
        "touched_max_rel_gap": gaps(slice(0, touched)),
        "untouched_max_rel_gap": gaps(slice(touched, None)), **note}),
        flush=True)


def fused_leg(rng) -> None:
    """One chip, 1,048,576 slots into the FM's tables (``--ffm``: the
    field-aware FM's one): the update on each route, one step from the
    same state compared, then timed."""
    n = B * K
    flat = batch_ids(11, B).reshape(-1)
    ids = jnp.asarray(flat)
    real = ids != W1 - 1
    g_w = jnp.asarray(rng.normal(size=n).astype(np.float32)) * real
    g_v = jnp.asarray(rng.normal(size=(n, F)).astype(np.float32)) \
        * real[:, None]
    touched = np.unique(flat)[:4096]
    at = jnp.asarray(np.concatenate([touched, np.setdiff1d(
        rng.integers(0, W1 - 1, 8192), flat)[:4096]]))
    count = jnp.asarray(7, jnp.int32)
    tag = {"slots": n}
    rows = {}
    for name, fn in (("two_passes", two_passes), ("fused_update", fused)):
        state = adam_state()
        run = jax.jit(fn, donate_argnums=0)
        state = jax.block_until_ready(run(state, count, ids, g_w, g_v))
        rows[name] = sampled(state, at)
        timed_in_place(name, run, state, count, ids, g_w, g_v, **tag)
        del state
    update_check("fused_check", rows["fused_update"], rows["two_passes"],
                 len(touched), **tag)

    cols = columns(g_w, g_v)
    del g_w, g_v
    fused_kernel_pieces(ids, cols, W1, (1, 2, 4) if FFM else (1, 2, 4, 8),
                        **tag)
    if not FFM:
        ragged_fused_leg(rng)


# shorter ladders than sorted_walk.RUNGS' sixteen, for ``--fused --ladders``:
# a pair pays a branch a halving, so a narrow payload, whose tile-products
# are cheap, may be better off with fewer rungs (PERF.md §6, PR 46)
LADDERS = ((1, 2, 4, 8, 16, 32), (1, 2, 3, 4, 6, 8, 12, 16, 24, 32),
           (4, 8, 16, 32), (8, 16, 24, 32), (16, 32))


def fused_kernel_pieces(flat, cols, num_rows: int, blocks_a_step=None,
                        **tag):
    """The update's kernel alone on the slots ``flat`` [N] with the
    cotangent columns ``cols`` [width, N]: the walk's books of them
    (``sorted_walk.walk_books``, the function ``learner.walk_books()``
    counts a cell's batch with; set as the gauges ``walk_books{what=}``
    and printed whole as the piece ``walk_books``), then the kernel with
    its epilogue with the slots and with none (the leaves' stream) at each
    of ``blocks_a_step`` blocks a grid step (by default the one step the op
    itself takes), and at the op's own step also with every pair contracted
    over its whole block, as until PR 46."""
    width = cols.shape[0]
    bounds, ids_s, pay = jax.block_until_ready(jax.jit(
        lambda i, c: sw.sorted_payload(i, c, num_rows))(flat, cols))
    # the other side of a wide payload: the lines cut, transposed and split
    # by XLA, as until PR 47
    columns_of = jax.jit(lambda p: sw.split_payload(p.T[:width], p.shape[0]))
    sides = {sw.slot_layout(width): pay}
    if "lines" in sides:
        sides["columns"] = timed("xla_split_of_lines", columns_of, pay,
                                 **tag)
    books = {what: int(x) for what, x in jax.jit(
        lambda i: sw.walk_books(i, num_rows))(flat).items()}
    telemetry.set_walk_books(books)
    print(json.dumps({"piece": "walk_books", **books, **tag}), flush=True)
    made, whole = books["tile_products"], books["whole_block_tile_products"]
    empty = jnp.full_like(bounds, bounds[0, -1])
    scalars = () if FFM else (ADAM.bias(jnp.asarray(8, jnp.int32)),)
    per = EPILOGUE.leaves
    tiles = sw.ladder(sw.BLOCK_IDS)[-1:]
    taken = gs._epilogue_blocks_a_step(
        width, sw.BLOCK_IDS, -(-num_rows // sw.BLOCK_IDS))

    def kern(state, bo, ids_s, pay, blocks, rungs=sw.ladder(sw.BLOCK_IDS)):
        # (grad_scatter_pallas' own call, with the ladder said aloud; the
        # slots are operands: closed over, they are compiled in as 250 MB
        # of constants)
        out = gs._scatter_call(
            bo, ids_s, pay, *scalars,
            *(x.T if x.ndim == 2 else x for t in state for x in t),
            num_rows=num_rows, trailing=TRAILING, epilogue=EPILOGUE,
            blocks_a_step=blocks, rungs=rungs, block_ids=sw.BLOCK_IDS,
            chunk_slots=sw.CHUNK_SLOTS, interpret=False, name=None)
        return tuple(tuple(x.T if x.ndim == 2 else x
                           for x in out[per * i:per * (i + 1)])
                     for i in range(len(TRAILING)))

    def jitted(blocks, **how):
        return jax.jit(functools.partial(kern, blocks=blocks, **how),
                       donate_argnums=0)

    # one step from the same state on each ladder: the same bits
    bits = jax.jit(lambda state: [
        jnp.sum(jax.lax.bitcast_convert_type(x, jnp.uint32),
                dtype=jnp.uint32) for t in state for x in t])
    sums = [[int(x) for x in bits(jitted(taken, **how)(
        adam_state(rows=num_rows), bounds, ids_s, side))]
        for how, side in [({}, pay), ({"rungs": tiles}, pay)] + [
            ({}, sides["columns"])] * ("lines" in sides)]
    print(json.dumps({"piece": "fused_kernel_bits", "window": sums[0],
                      "whole_block": sums[1],
                      "equal": all(x == sums[0] for x in sums),
                      "sides": sorted(sides),
                      "blocks_a_step": taken, **tag}), flush=True)

    state = adam_state(rows=num_rows)
    for blocks in blocks_a_step or (taken,):
        run = jitted(blocks)
        state = timed_in_place("fused_kernel", run, state, bounds, ids_s,
                               pay, blocks_a_step=blocks, tile_products=made,
                               tile_products_whole_block=whole, **tag)
        state = timed_in_place("fused_kernel_no_slot", run, state, empty,
                               ids_s, pay, blocks_a_step=blocks, **tag)
        if blocks != taken:
            continue
        if "lines" in sides:
            state = timed_in_place(
                "fused_kernel_columns", run, state, bounds, ids_s,
                sides["columns"], blocks_a_step=blocks, **tag)
        state = timed_in_place(
            "fused_kernel_whole_block", jitted(blocks, rungs=tiles), state,
            bounds, ids_s, pay, blocks_a_step=blocks, **tag)
        for rungs in LADDERS if "--ladders" in sys.argv else ():
            state = timed_in_place(
                "fused_kernel_ladder", jitted(blocks, rungs=rungs), state,
                bounds, ids_s, pay, blocks_a_step=blocks, rungs=rungs,
                tile_products=int(sw.tile_counts(
                    bounds, sw.round_up(num_rows, sw.BLOCK_IDS),
                    sw.BLOCK_IDS, rungs)[0]), **tag)


def ragged_fused_leg(rng) -> None:
    """The Adam kernel's pieces on one batch of kddb_fm's slots (see
    :func:`ragged_leg`)."""
    import bench_slot_rows as ragged     # the cell's generator, as set there

    num_rows = ragged.W1
    flat = jnp.asarray(ragged_slots(ragged))
    cols = jnp.asarray(rng.normal(size=(F + 1, flat.shape[0])).astype(
        np.float32)) * (flat != num_rows - 1)
    fused_kernel_pieces(flat, cols, num_rows, slots=flat.shape[0],
                        table_rows=num_rows)


def kernel_pieces(flat, lane_major, num_rows: int, **tag):
    """The forward's kernel alone on the slots ``flat`` [N]: their sort,
    the tile-products the kernel makes of them beside those of whole
    blocks (``table_gather_tile_counts``), the kernel, and the kernel with
    no slot
    (the tables' stream). Returns the sort and the sorted rows."""
    trailing = tuple(tuple(t.shape[:-1]) for t in lane_major)
    width = sum(sw.widths(trailing))
    sorted_slots = timed("sort_slots", jax.jit(
        lambda i: sw.sort_slots(i, num_rows)), flat, **tag)
    bounds, ids_s, _ = sorted_slots
    made, whole = map(int, tg.table_gather_tile_counts(
        flat, num_rows,
        blocks_a_step=tg._blocks_a_step(num_rows, width, sw.BLOCK_IDS)))
    kern = lambda bo, i, *t, **how: tg.table_gather_pallas(   # noqa: E731
        bo, i, *t, num_rows=num_rows, trailing=trailing, **how)
    side = sw.slot_layout(width)
    kern = functools.partial(kern, layout=side)
    rows_s = timed("gather_kernel", kern, bounds, ids_s, *lane_major,
                   tile_products=made, tile_products_whole_block=whole,
                   layout=side, **tag)
    empty = jnp.full_like(bounds, bounds[0, -1])
    timed("gather_kernel_no_slot", kern, empty, ids_s, *lane_major, **tag)
    if side == "lines":
        # the other side of a wide table: the kernel's lane-major rows and
        # XLA's transposition and padding to lines, as until PR 47
        cols_s = timed("gather_kernel_columns", functools.partial(
            kern, layout="columns"), bounds, ids_s, *lane_major, **tag)
        lines = timed("xla_lines_of_columns", jax.jit(
            lambda r: sw.lines_of_cols(r[:width])), cols_s, **tag)
        print(json.dumps({"piece": "gather_kernel_bits", "equal": bool(
            jnp.array_equal(lines, rows_s)), **tag}), flush=True)
    return sorted_slots, rows_s


def forward_check(ids, tables, **tag) -> None:
    """The whole forward on each route, which must agree value for value."""
    want = timed("forward_xla", jax.jit(lambda i, *t: tuple(
        jnp.take(x, i, axis=0) for x in t)), ids, *tables, **tag)
    got = timed("forward_kernel", jax.jit(lambda i, *t: tuple(
        r.reshape(i.shape + r.shape[1:]) for r in tg.table_rows_kernel(
            i.reshape(-1), t)[0])), ids, *tables, **tag)
    print(json.dumps({
        "piece": "forward_check", **tag,
        "values_equal": all(bool(jnp.array_equal(a, b))
                            for a, b in zip(got, want))}), flush=True)


def lane_major_of(tables):
    return jax.block_until_ready(jax.jit(lambda *t: tuple(
        x.T if x.ndim == 2 else x for x in t))(*tables))


def gather_leg(rng) -> None:
    """One chip: the pieces of the forward at 1,048,576 and 262,144 slots,
    and both routes whole; without ``--ffm`` the kernel's pieces at
    kddb_fm's shape too."""
    shapes = ((W1, F),) if FFM else ((W1,), (W1, F))
    tables = tuple(jax.random.normal(jax.random.key(i), shape, jnp.float32)
                   for i, shape in enumerate(shapes))
    lane_major = lane_major_of(tables)
    width = sum(x.shape[1] if x.ndim == 2 else 1 for x in tables)
    for rows in (B, B // 4):
        n = rows * K
        ids = jnp.asarray(batch_ids(11, rows))
        tag = {"slots": n, "width": width}
        for i, t in enumerate(tables):
            timed("xla_take", jax.jit(lambda t, i: jnp.take(t, i, axis=0)),
                  t, ids, table=i, columns=t.shape[1:], **tag)
        forward_check(ids, tables, **tag)
        (_, _, perm), rows_s = kernel_pieces(ids.reshape(-1), lane_major, W1,
                                             **tag)
        inverse = timed("sort_inverse", jax.jit(lambda p: jax.lax.sort(
            (p, jax.lax.iota(jnp.int32, n)), num_keys=1)[1]), perm, **tag)
        timed("unpermute", jax.jit(
            sw.permute_lines if sw.slot_layout(width) == "lines" else
            lambda r, p: sw.permute_columns(r[:width], p)), rows_s, inverse,
            **tag)
        del rows_s
    del tables, lane_major
    if not FFM:
        ragged_leg()
    step_leg()


def ragged_slots(ragged) -> np.ndarray:
    """One batch of kddb_fm's slots: ``B`` ragged rows of
    ``ragged_zipf_libsvm`` (``ragged``: bench_slot_rows, the cell's
    generator as set there), the padding on the last row."""
    _, ids, _ = ragged.gen.draw_rows(ragged.PARAMS,
                                     np.random.SeedSequence(11), B)
    flat = np.full(sw.round_up(len(ids), B), ragged.W1 - 1, np.int32)
    flat[:len(ids)] = ids + 1                   # the cell's first_id is 1
    return flat


def ragged_leg() -> None:
    """The kernel's pieces on one batch of kddb_fm's slots (ragged rows of
    ``ragged_zipf_libsvm``, 29,890,097 table rows, the padding on the last
    row): ids that fill a chunk's window where the ELL cells' leave it
    sparse."""
    import bench_slot_rows as ragged     # the cell's generator, as set there

    num_rows = ragged.W1
    flat = ragged_slots(ragged)
    tables = tuple(jax.random.normal(jax.random.key(i), shape, jnp.float32)
                   for i, shape in enumerate(((num_rows,), (num_rows, 8))))
    tag = {"slots": len(flat), "width": 9, "table_rows": num_rows}
    forward_check(jnp.asarray(flat), tables, **tag)
    kernel_pieces(jnp.asarray(flat), lane_major_of(tables), num_rows, **tag)


def step_leg() -> None:
    """The learner's whole step at the cell's shape with the forward on
    each route (the backward on its own): what the route's constants have
    to predict is the difference."""
    from dmlc_tpu.models import FFMLearner, FMLearner
    from dmlc_tpu.ops.sparse import EllBatch

    ids = batch_ids(11, B)
    real = ids != W1 - 1
    batch = EllBatch(
        jnp.asarray(ids), jnp.asarray(real.astype(np.float32)),
        jnp.asarray((np.arange(B) % 2).astype(np.float32)),
        jnp.ones(B, jnp.float32),
        jnp.asarray(np.where(real, np.arange(K) % 11, 0).astype(np.uint8))
        if FFM else None)
    modelled = tg.table_gather_route
    for route in ("xla", "kernel"):
        tg.table_gather_route = lambda *a, r=route: r
        model = (FFMLearner(W1 - 1, 11, 4) if FFM else
                 FMLearner(num_col=W1 - 1, num_factors=F, layout="ell"))
        timed("step", lambda: model.step(batch), reps=8, forward=route,
              slots=B * K)
        del model
    tg.table_gather_route = modelled


def main() -> None:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("bench_grad_scatter: needs a TPU")
    print(json.dumps({"device": dev.device_kind, "jax": jax.__version__,
                      "devices": jax.device_count()}))
    rng = np.random.default_rng(7)
    if "--gather" in sys.argv:
        gather_leg(rng)
        return
    if "--fused" in sys.argv:
        fused_leg(rng)
        return
    for rows in (B, B // 4):
        n = rows * K
        ids = jnp.asarray(batch_ids(11, rows).reshape(-1))
        g_w = jnp.asarray(rng.normal(size=n).astype(np.float32))
        g_v = jnp.asarray(rng.normal(size=(n, F)).astype(np.float32))
        real = ids != W1 - 1
        g_w, g_v = g_w * real, g_v * real[:, None]   # sink slots: gradient 0
        tag = {"slots": n}

        xla = jax.jit(lambda i, a, b: gs.table_grad_xla(
            i, cotangents(a, b), W1))
        *_, dv0 = timed("xla_scatter_add", xla, ids, g_w, g_v, **tag)

        if rows == B and "--sorts" in sys.argv:
            cols = columns(g_w, g_v)
            keys = jnp.broadcast_to(ids[None], cols.shape)
            timed("sort_keys_only", jax.jit(jax.lax.sort), ids, **tag)
            perm = timed("sort_key_iota", jax.jit(lambda i: jax.lax.sort(
                (i, jax.lax.iota(jnp.int32, n)), num_keys=1)[1]), ids, **tag)
            timed("permute_rows", jax.jit(lambda p, x: jnp.take(
                x, p, axis=0)), perm, jnp.pad(cols.T, (
                    (0, 0), (0, -cols.shape[0] % 16))), **tag)
            timed("permute_cols", jax.jit(lambda p, x: jnp.take(
                x, p, axis=1)), perm, cols, **tag)
            timed("sort_batched_9", jax.jit(lambda k, c: jax.lax.sort(
                (k, c), dimension=1, num_keys=1)), keys, cols, **tag)
            timed("sort_batched_1", jax.jit(lambda k, c: jax.lax.sort(
                (k, c), dimension=1, num_keys=1)), keys[:1], cols[:1], **tag)
            sorted_ids = jnp.sort(ids)
            timed("searchsorted_scan", jax.jit(lambda s: jnp.searchsorted(
                s, jnp.arange(-(-W1 // 2048) + 1, dtype=jnp.int32) * 2048)),
                sorted_ids, **tag)

        grid = [(2048, 128), (4096, 128), (8192, 128)]
        if rows == B and "--grid" in sys.argv:
            grid += [(1024, 128), (16384, 128), (2048, 256), (4096, 256),
                     (8192, 256), (4096, 512)]
        for t_ids, c_slots in grid:
            shape = {"block_ids": t_ids, "chunk_slots": c_slots, **tag}
            prep = jax.jit(lambda i, a, b: sw.sorted_payload(
                i, columns(a, b), W1, t_ids, c_slots))
            bounds, ids_s, pay = jax.block_until_ready(prep(ids, g_w, g_v))
            kern = lambda bo, i, p: gs.grad_scatter_pallas(   # noqa: E731
                bo, i, p, num_rows=W1, trailing=TRAILING, block_ids=t_ids,
                chunk_slots=c_slots)
            *_, dv_t = timed("kernel", kern, bounds, ids_s, pay, **shape)
            gap = float(jnp.abs(dv_t.T - dv0).max())
            zeros_same = bool(jnp.all((dv_t.T == 0) == (dv0 == 0)))
            print(json.dumps({"piece": "kernel_check", "max_abs_gap": gap,
                              "zero_rows_agree": zeros_same, **shape}),
                  flush=True)
            if c_slots == 128:
                empty = jnp.full_like(bounds, bounds[0, -1])
                timed("kernel_no_slot", kern, empty, ids_s, pay, **shape)
            if (t_ids, c_slots) == (2048, 128):
                sentinel = int(bounds[0, -1])
                b2, i2, p2 = jax.block_until_ready(prep(
                    jnp.where(real, ids, sentinel), g_w, g_v))
                timed("kernel_sink_skipped", kern, b2, i2, p2, **shape)
            del dv_t

        timed("step_a_sorted_payload", jax.jit(lambda i, a, b:
              sw.sorted_payload(i, columns(a, b), W1)), ids, g_w, g_v,
              **tag)
        timed("steps_a_b", jax.jit(lambda i, a, b: gs.table_grad_kernel(
            i, cotangents(a, b), W1)), ids, g_w, g_v, **tag)
        del dv0

    if "--variadic" in sys.argv:
        n = B * K
        ids = jnp.asarray(batch_ids(11, B).reshape(-1))
        cols = [jnp.asarray(rng.normal(size=n).astype(np.float32))
                for _ in range(F + 1)]
        timed("sort_variadic_1_9", jax.jit(lambda i, *c: jax.lax.sort(
            (i, *c), num_keys=1)), ids, *cols, slots=n)


if __name__ == "__main__":
    main()
