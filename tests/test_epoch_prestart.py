"""The epoch boundary of a consumer that loops (PR 37): once a ``reset()``
has followed an epoch's end, ``DeviceIter`` starts the next epoch's convert
pool AT an epoch's end, ahead of the ``reset()`` that adopts it
(``DeviceIter._prestart_next_epoch``). What a consumer can see between the
end and the ``reset()`` stays the ended epoch's; the batches are those of a
producer started lazily; the paths it leaves alone stay lazy."""

import threading
import time

import numpy as np
import pytest

from dmlc_tpu.data import create_parser
from dmlc_tpu.data.device import DeviceIter
from dmlc_tpu.utils import telemetry

ROWS, BATCH, COLS = 400, 64, 40
PER_EPOCH = -(-ROWS // BATCH)


def _corpus(tmp_path):
    rng = np.random.default_rng(5)
    path = tmp_path / "c.libsvm"
    with open(path, "w") as f:
        for i in range(ROWS):
            ids = np.sort(rng.choice(COLS, rng.integers(1, 9),
                                     replace=False)) + 1
            f.write(f"{i % 2} " + " ".join(
                f"{j}:{0.25 + j / 64:.6g}" for j in ids) + "\n")
    return str(path)


KINDS = {
    "bcoo": dict(layout="bcoo", nnz_bucket=64),
    "ell": dict(layout="ell", max_nnz=8),
    "dense": dict(layout="dense"),
}


def _iter(path, kind, **more):
    kwargs = dict(num_col=COLS + 1, batch_size=BATCH, **KINDS[kind])
    kwargs.update(more)
    return DeviceIter(create_parser(path + "?format=libsvm", **{
        k: kwargs.pop(k) for k in ("block_cache",) if k in kwargs}), **kwargs)


def _digest(batch):
    import jax

    return [np.asarray(leaf).tobytes()
            for leaf in jax.tree_util.tree_leaves(batch)]


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_a_looping_consumer_finds_the_next_epoch_started(tmp_path, kind):
    it = _iter(_corpus(tmp_path), kind)
    epochs, ahead, ended = [], [], []
    for _ in range(4):
        epochs.append([_digest(b) for b in it])
        # ended, and it stays so until reset(): the producer that runs now
        # is the next epoch's
        with pytest.raises(StopIteration):
            next(it)
        stats = it.stats()
        ahead.append((it._prestarted, stats["epochs_prestarted"]))
        ended.append((stats["batches"], it.state_dict()))
        it.reset()
        assert it.stats()["batches"] == 0
    it.close()
    # the first end is any consumer's; the reset() after it shows the loop
    assert ahead == [(False, 0), (True, 0), (True, 1), (True, 2)]
    assert len(epochs[0]) == PER_EPOCH
    for later in epochs[1:]:
        assert later == epochs[0]
    # between its end and the reset() an epoch's books are its own, whether
    # or not the next one has started
    assert [n for n, _ in ended] == [PER_EPOCH] * 4
    assert all(state == ended[0][1] for _, state in ended)
    assert ended[0][1]["batches"] == PER_EPOCH


def test_the_head_start_runs_while_the_consumer_is_away(tmp_path):
    it = _iter(_corpus(tmp_path), "bcoo")
    label = it.stats()["pipeline"]
    for _ in range(3):
        for _batch in it:
            pass
        time.sleep(0.3)      # the steps still queued on the device
        it.reset()
    it.close()
    spans = telemetry.spans_snapshot(label)

    def one(name, **labels):
        found = [s for s in spans if s["name"] == name and all(
            s["labels"].get(k) == v for k, v in labels.items())]
        assert len(found) == 1, (name, labels, len(found))
        return found[0]

    def end(s):
        return s["start_ns"] + s["dur_ns"]

    # epoch 1 was started by its first pull, epoch 2 by epoch 1's end: its
    # first batch was converted before the reset() and only put after it
    lazy, early = one("epoch_reset", epoch=1), one("epoch_reset", epoch=2)
    assert lazy["start_ns"] < one("producer_start", epoch=1)["start_ns"]
    assert end(one("producer_start", epoch=2)) <= early["start_ns"]
    assert end(one("convert", epoch=2, batch=0)) <= early["start_ns"]
    assert end(early) <= one("first_batch", epoch=2)["start_ns"]
    assert early["start_ns"] <= one("dispatch", epoch=2, batch=0)["start_ns"]
    first_batch = one("first_batch", epoch=2)
    assert first_batch["start_ns"] <= one("next", epoch=2,
                                          batch=0)["start_ns"]
    # every epoch still has one of each span the boundary's readers pair
    for epoch in (1, 2, 3):
        for name in ("epoch_reset", "producer_start"):
            one(name, epoch=epoch)


@pytest.mark.parametrize("kind", ["bcoo", "dense"])
def test_a_restore_drops_the_head_start(tmp_path, kind):
    it = _iter(_corpus(tmp_path), kind)
    for _batch in it:
        pass
    it.reset()
    want, state = [], None
    for n, batch in enumerate(it):
        if n == 2:
            state = it.state_dict()      # after the third hand-out
        if n > 2:
            want.append(_digest(batch))
    assert it._prestarted and it._epoch == 2
    it.load_state(state)
    assert not it._prestarted and it._epoch == 1     # still that epoch
    assert it.stats()["batches"] == 3
    assert [_digest(b) for b in it] == want
    # the restored epoch ran to its end: the loop goes on from there
    assert it._prestarted
    it.reset()
    assert len([1 for _ in it]) == PER_EPOCH
    assert it.stats()["epochs_prestarted"] == 1
    it.close()


def test_a_reset_inside_an_epoch_starts_over_lazily(tmp_path):
    it = _iter(_corpus(tmp_path), "ell")
    first = [_digest(b) for b in it]
    it.reset()
    assert next(it) is not None and next(it) is not None
    it.reset()                            # mid-epoch: nothing had ended
    assert not it._prestarted and it._host_iter_obj is None
    assert [_digest(b) for b in it] == first
    assert it._prestarted
    it.reset()
    it.reset()                            # twice: the adopted one goes too
    assert not it._adopted and it._host_iter_obj is None
    assert [_digest(b) for b in it] == first
    it.close()


class _Remote:
    """A source that looks like a service client to DeviceIter: it rewinds
    by asking other processes, which nobody does on speculation."""

    def __init__(self, inner):
        self._inner = inner

    def service_stats(self):
        return {}

    def __getattr__(self, name):
        return getattr(self._inner, name)


@pytest.mark.parametrize("what", ["snapshot", "natural", "service"])
def test_the_paths_it_leaves_alone_stay_lazy(tmp_path, what):
    path = _corpus(tmp_path)
    if what == "snapshot":
        it = DeviceIter(create_parser(path + "?format=libsvm"),
                        num_col=COLS + 1, batch_size=BATCH, layout="dense",
                        snapshot=str(tmp_path / "c.snapshot"))
    elif what == "natural":
        it = DeviceIter(create_parser(path + "?format=libsvm"),
                        num_col=COLS + 1, batch_size=None, layout="bcoo")
    else:
        it = DeviceIter(_Remote(create_parser(path + "?format=libsvm")),
                        num_col=COLS + 1, batch_size=BATCH, layout="dense")
    counts = []
    for _ in range(3):
        counts.append(sum(1 for _ in it))
        assert not it._prestarted
        it.reset()
        assert it._host_iter_obj is None      # the next pull will start it
    assert len(set(counts)) == 1 and counts[0] > 0
    assert it.stats()["epochs_prestarted"] == 0
    it.close()


def test_warm_block_cache_epochs_are_the_cold_ones_started_early_or_not(
        tmp_path):
    path = _corpus(tmp_path)
    it = _iter(path, "bcoo", block_cache=str(tmp_path / "c.blockcache"))
    epochs = []
    for _ in range(4):
        epochs.append([_digest(b) for b in it])
        it.reset()
    stats = it.stats()
    it.close()
    assert stats["cache_state"] == "warm" and stats["epochs_prestarted"] == 3
    for warm in epochs[1:]:
        assert warm == epochs[0]


def test_close_takes_the_head_start_down(tmp_path):
    before = threading.active_count()
    it = _iter(_corpus(tmp_path), "dense", convert_workers=3)
    for _ in range(2):
        for _batch in it:
            pass
        if not it._prestarted:
            it.reset()
    assert it._prestarted and it._host_iter_obj is not None
    pool = it._host_iter_obj
    it.close()
    deadline = time.time() + 5
    while any(t.is_alive() for t in pool._threads) and time.time() < deadline:
        time.sleep(0.01)
    assert not any(t.is_alive() for t in pool._threads)
    assert threading.active_count() <= before + 1   # the parser's own, if any
