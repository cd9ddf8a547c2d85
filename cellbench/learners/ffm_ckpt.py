"""Adapter between the harness and ``dmlc_tpu.models.FFMLearner`` run as a
job that saves and resumes (PR 41, configuration ``kdd12_ffm_ckpt``).

The mathematics, the reference, the comparison's six numbers and the
controls are ``learners/ffm.py``'s; what differs is when the learner's
state is written and where it comes from:

* **Set-up is a resumed job.** The adapter builds the learner from the
  seed, saves that start through the program's own ``save_async`` (the
  same device copy and drain as the window's save: the snapshot's program
  is compiled here), drops the learner, builds another **from another
  seed** and brings it to the saved state with ``restore``: the learner
  the harness steps holds nothing the file did not give it. The
  reference still draws its own start from the run's seed and knows
  nothing of a file, so ``learners/ffm.py``'s six numbers (``untouched_gap``
  exact) now decide whether what was written came back.
* **One save in the window**, begun inside the adapter's ``step`` call
  that ends the window's first epoch, after that step is dispatched.
  ``step`` and the position are the adapter's own count.
* **After the window** (``checksum_fold`` is the harness's first call
  after the drain): ``wait()``, then the read-back with the plain reader
  (``cellbench/reference/ckpt_plain_read.py``, which imports nothing of
  the program) against what a jitted probe took from the learner's live
  tables between the step the save follows and the save: 264 touched and
  256 untouched sample rows of ``W`` and ``G``, and the wrapping uint32
  sum of each table's bits. Stream order makes that the state after
  exactly ``step`` steps, whatever the save's own copy holds.

Four more numbers join the comparison, each 0 in a sound run:
``ckpt_rows_gap``, ``ckpt_sum_gap``, ``ckpt_step_gap``,
``ckpt_unpublished``. The harness hands a plugin ``config`` and ``seed``
only, so what ``reference_digest``, ``Adapter`` and ``compare`` share goes
through this module (``_RUN``), and the checkpoints live in a directory of
the plugin's own under ``run.CACHE``, emptied when the adapter is built.
"""

from __future__ import annotations

import gc
import os
import shutil
import time

import numpy as np

from cellbench.learners import ffm as _ffm
from cellbench.reference import ckpt_plain_read
# at import, not in Adapter: a program that cannot save (the parent of
# PR 41) fails here, at once, before the corpus and the reference
from dmlc_tpu.models import _checkpoint as _program_checkpoint  # noqa: F401

CKPT_NUMBERS = ("ckpt_rows_gap", "ckpt_sum_gap", "ckpt_step_gap",
                "ckpt_unpublished")
W, G = "params.w", "opt_state.0.sum_of_squares.w"
_RUN: dict = {}      # the run's reference ids and its adapter


def reference_digest(config: dict, seed: int, corpus_path: str, **how):
    ref = _ffm.reference_digest(config, seed, corpus_path, **how)
    _RUN["sample_ids"] = np.concatenate(
        [ref["touched_ids"], ref["untouched_ids"]]).astype(np.int64)
    return ref


def compare(ref: dict, losses: list, grad_norms: list, update_norms: list,
            touched: dict, untouched: dict) -> dict:
    """``learners/ffm.py``'s numbers on the restored learner, and the four
    of the checkpoints themselves from the run's adapter."""
    out = _ffm.compare(ref, losses, grad_norms, update_norms, touched,
                       untouched)
    adapter = _RUN.get("adapter")
    out.update(adapter.ckpt_numbers() if adapter is not None
               else {k: float("inf") for k in CKPT_NUMBERS})
    return out


def control_numbers(config: dict, seed: int, corpus_path: str,
                    ref: dict) -> dict:
    """``learners/ffm.py``'s two controls, and for the checkpoint's own
    numbers a file written in bfloat16 and widened when read, put in the
    file's place: the reference's sampled rows of ``W`` and ``G`` against
    themselves rounded (its step and its publication are sound)."""
    import jax.numpy as jnp

    out = _ffm.control_numbers(config, seed, corpus_path, ref)
    rows = np.concatenate([ref["touched"]["w"], ref["touched"]["g"]])
    low = np.asarray(jnp.asarray(rows).astype(jnp.bfloat16), np.float32)
    bits = lambda x: int(np.ascontiguousarray(x).view(np.uint32).sum(  # noqa: E731
        dtype=np.uint64)) % (1 << 32)
    out.update(ckpt_rows_gap=float(np.abs(low - rows).max()),
               ckpt_sum_gap=float(abs(bits(low) - bits(rows))),
               ckpt_step_gap=0.0, ckpt_unpublished=0.0)
    return out


class _Position:
    """What ``save_async`` asks of a ``device_iter``: the harness keeps
    the iterator, so the position is the adapter's own count of the
    batches it was handed since the epoch began."""

    def __init__(self, batches: int):
        self.batches = batches

    def state_dict(self) -> dict:
        return {"kind": "batches", "batches": self.batches}


class Adapter(_ffm.Adapter):
    def __init__(self, config: dict, seed: int, mesh=None):
        from cellbench import run

        # a learner closes a cycle with its own jitted step: the one of an
        # adapter built before this one (tools/limits.py builds one a
        # seed) gives its 5.25 GB back only to the collector
        _RUN.pop("adapter", None)
        gc.collect()
        super().__init__(config, seed, mesh=mesh)
        self.how = config["checkpoint"]
        self.steps_per_epoch = -(-config["rows"] // config["batch_size"])
        self.dir = os.path.join(run.CACHE, "ckpt", config["name"])
        shutil.rmtree(self.dir, ignore_errors=True)
        self.steps = 0
        self.saves = []         # (handle, step, position, probe) asked for
        self.unpublished = 0
        self._probe = None
        self.seconds = {}
        _RUN["adapter"] = self
        # ---- set-up is a resumed job ----
        t0 = time.perf_counter()
        paths = self._save().wait()
        self.seconds["setup_save_s"] = time.perf_counter() - t0
        run.log(f"checkpoint: the start saved in "
                f"{self.seconds['setup_save_s']:.3f} s: "
                f"{self.learner.checkpoint_stats()['last_save']}")
        del self.learner
        gc.collect()
        t0 = time.perf_counter()
        # the learner the harness steps: another seed's, so that nothing
        # it holds after the restore can have come from the draw
        self.learner = _ffm.Adapter(config, self.seed + 1, mesh=mesh).learner
        found = self.learner.latest(self.dir)
        if found is None or found["paths"] != paths:
            self.unpublished += 1      # not what a restarted job would find
        back = self.learner.restore(paths[0])
        self.seconds["restore_s"] = time.perf_counter() - t0
        self.restored_step = back["step"]
        # the probe of what came back: the start's own bits, and the
        # program the window's probe runs (restored arrays are committed
        # to their device, the drawn ones were not: another compilation)
        self.restored_probe = self._probe_of()
        run.log(f"checkpoint: a learner of another seed restored in "
                f"{self.seconds['restore_s']:.3f} s: "
                f"{self.learner.checkpoint_stats()['last_restore']}")

    # ---- the saves ----
    def _probe_of(self):
        """The jitted probe of the learner's live tables: sample rows by
        id and the wrapping sum of the bits, of ``W`` and ``G``. It is
        dispatched after the step a save follows and before the save, so
        in stream order it reads the state after exactly that many steps,
        and knows nothing of the snapshot the save takes. The ids are an
        argument, so every seed runs the one program."""
        import jax
        import jax.numpy as jnp

        if self._probe is None:
            def one(table, ids):
                return jnp.take(table, ids, axis=0), jnp.sum(
                    jax.lax.bitcast_convert_type(table, jnp.uint32),
                    dtype=jnp.uint32)

            def ckpt_probe(w, g, ids):
                return one(w, ids), one(g, ids)

            self._probe = jax.jit(ckpt_probe)
        return self._probe(self.learner.params.w, self.learner.accumulators,
                           jnp.asarray(_RUN["sample_ids"], jnp.int32))

    def _save(self):
        position = self.steps % self.steps_per_epoch or (
            self.steps_per_epoch if self.steps else 0)
        probe = self._probe_of()
        handle = self.learner.save_async(
            self.dir, step=self.steps, device_iter=_Position(position),
            keep_last=self.how["keep_last"])
        self.saves.append((handle, self.steps, position, probe))
        return handle

    def step(self, batch):
        loss = self.learner.step(batch)
        self.steps += 1
        # the window's first epoch boundary: set-up made one epoch's
        # calls, so this is the window's last step of its first epoch
        if self.steps == 2 * self.steps_per_epoch and len(self.saves) == 1:
            self._save()
        return loss

    def checksum_fold(self):
        """The harness's first call after the drain: the window's save is
        waited for here, outside the window."""
        from cellbench import run

        t0 = time.perf_counter()
        for handle, *_ in self.saves:
            try:
                handle.wait()
            except Exception as exc:  # noqa: BLE001 - counted, compared
                run.log(f"checkpoint: a save failed: {exc!r}")
        run.log(f"checkpoint: waited {time.perf_counter() - t0:.3f} s "
                f"after the window for {len(self.saves)} save(s); "
                f"{self.learner.checkpoint_stats()}")
        return super().checksum_fold()

    # ---- the numbers ----
    def ckpt_numbers(self) -> dict:
        """The four numbers of the checkpoints: the window's save read
        back by the plain reader against the probe of the live tables,
        every save against the publish records of the store's journal
        (read by the plain reader too)."""
        from cellbench import run
        out = {"ckpt_rows_gap": 0.0, "ckpt_sum_gap": 0.0,
               "ckpt_step_gap": float(abs(self.restored_step - 0)),
               "ckpt_unpublished": float(self.unpublished)}
        due = self.steps >= 2 * self.steps_per_epoch
        if due and len(self.saves) < 1 + self.how["saves_in_window"]:
            run.log(f"checkpoint: {len(self.saves) - 1} saves in the "
                    f"window, the cell asks {self.how['saves_in_window']}")
            out["ckpt_unpublished"] += 1
        listed = ckpt_plain_read.published(self.dir)
        ids = _RUN["sample_ids"]
        for n, (handle, step, position, probe) in enumerate(self.saves):
            paths = handle.paths if handle.done() else []
            if not paths or any(os.path.basename(p) not in listed
                                or not os.path.exists(p) for p in paths):
                out["ckpt_unpublished"] += 1
                continue
            if n == 0 and len(self.saves) > 1:
                # the start: read back by restore in set-up; what the
                # restored learner held against what the saved one did
                for (rows, total), (back, back_total) in zip(
                        probe, self.restored_probe):
                    out["ckpt_rows_gap"] = max(out["ckpt_rows_gap"], float(
                        np.abs(np.asarray(rows, np.float64)
                               - np.asarray(back, np.float64)).max()))
                    out["ckpt_sum_gap"] = max(out["ckpt_sum_gap"], float(
                        abs(int(total) - int(back_total))))
                continue
            t0 = time.perf_counter()
            try:
                plain = ckpt_plain_read.PlainCheckpoint(paths)
            except (ValueError, OSError, KeyError) as exc:
                run.log(f"checkpoint: the plain reader refuses: {exc}")
                out["ckpt_unpublished"] += 1
                continue
            header = plain.header
            out["ckpt_step_gap"] += abs(header["step"] - step) + abs(
                (header["iterator"] or {}).get("batches", -1) - position)
            (w, w_sum), (g, g_sum) = probe
            sums = plain.bit_sums()
            for name, rows, total in ((W, w, w_sum), (G, g, g_sum)):
                gap = np.abs(plain.rows(name, ids).astype(np.float64)
                             - np.asarray(rows, np.float64)).max()
                out["ckpt_rows_gap"] = max(out["ckpt_rows_gap"], float(gap))
                out["ckpt_sum_gap"] = max(out["ckpt_sum_gap"], float(
                    abs(sums[name] - int(total))))
            run.log(f"checkpoint: step {step} read back by the plain "
                    f"reader in {time.perf_counter() - t0:.3f} s: header "
                    f"step {header['step']}, position "
                    f"{header['iterator']}, bit sums {sums}")
        return out

    def ckpt_books(self) -> dict:
        """The per-layer readers' account (``readers/ckpt_books.py``)."""
        from dmlc_tpu.utils import telemetry

        out = dict(self.seconds)
        if len(self.saves) > 1:
            handle, step = self.saves[-1][0], self.saves[-1][1]
            spans = [s for s in telemetry.spans_snapshot()
                     if s["name"] == "ckpt_snapshot"
                     and s["labels"].get("step") == step]
            if spans:
                out["stall_ms"] = spans[-1]["dur_ns"] * 1e-6
            if handle.done() and handle.seconds.get("landed"):
                out["drain_gb_per_s"] = (handle.nbytes * 1e-9
                                         / handle.seconds["landed"])
                out["publish_s"] = handle.seconds["published"]
        return out
