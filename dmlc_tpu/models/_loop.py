"""Shared training-loop surface for the learners.

One implementation of step/fit_epoch/fit/accuracy — including the SPMD
step-count contract (``steps_per_epoch`` / ``max_steps``): every process in
a pod must execute the same number of collective steps per epoch or the
pod deadlocks; agree on the cap with :func:`dmlc_tpu.parallel.sync_min`.

Learners provide ``self._step(params, opt_state, batch)``,
``self._margin(params, batch) -> (margin, label, weight)`` and
``self._pred_from_margin(margin)``; :meth:`TrainLoopMixin._build_accuracy`
derives the jitted on-device metric from those (replicated scalar outputs,
so results are addressable on every process). ``self.params`` /
``self.opt_state`` / ``self.mesh`` attributes are assumed.

Two loop-wide contracts live here so every learner inherits them:

* **Donated step buffers.** :meth:`TrainLoopMixin._jit_step` compiles the
  update with ``donate_argnums=(0, 1)`` — the ``(params, opt_state)``
  input buffers are handed back to XLA so the outputs reuse their HBM
  instead of doubling peak parameter memory. The compiled callable is
  stamped with ``_donate_argnums`` so tests can pin the contract
  structurally (the CPU backend accepts but ignores donation, so
  ``is_deleted``-style checks would not hold under tier-1).

* **No per-step host sync.** The loop never forces a device→host transfer
  inside the epoch: losses and metric partials accumulate as device
  scalars and cross to the host once per epoch through
  :func:`host_scalar`, the loop's single sanctioned sync point. (The only
  other device→host traffic during an epoch is DeviceIter's sampled
  transfer sideband, which the loop does not control.) Keeping the epoch
  free of blocking syncs is what lets dispatch run ahead of the ingest
  pipeline and hide input latency.
"""

from __future__ import annotations

import functools
import re
from typing import Dict, Tuple

from dmlc_tpu.utils import telemetry as _telemetry
from dmlc_tpu.utils.timer import get_time

# one instruction of compiled HLO text, ``[ROOT] [%]name = type op(...)``,
# and the op_name its metadata may carry
_HLO_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+) = ")
_HLO_OP_NAME = re.compile(r"metadata=\{[^}]*?op_name=\"(?P<op_name>[^\"]*)\"")


def _abstract(tree):
    """``jax.ShapeDtypeStruct`` leaves for a pytree of jax OR host arrays
    (a numpy batch has no ``.sharding``; its dtype is the one jit would
    give it)."""
    import jax
    import numpy as np

    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(
            np.shape(x), jax.dtypes.result_type(x),
            sharding=getattr(x, "sharding", None)), tree)


def _scopes_of_text(text: str) -> Dict[str, str]:
    """``{instruction name: op_name}`` of compiled HLO text (``""`` where
    XLA gave an instruction none)."""
    out: Dict[str, str] = {}
    for line in text.splitlines():
        ins = _HLO_INSTRUCTION.match(line)
        if ins:
            op = _HLO_OP_NAME.search(line)
            out.setdefault(ins["name"], op["op_name"] if op else "")
    return out


def host_scalar(x) -> float:
    """Bring one device scalar to the host — the loop's sanctioned sync.

    Every device→host conversion the training loop performs funnels
    through here (once per epoch for the loss, twice per accuracy pass),
    so a regression test can monkeypatch this single name and count
    blocking syncs instead of auditing call sites.
    """
    return float(x)


class TrainLoopMixin:
    def _jit_step(self, step_fn, params_sh=None, batch_sh=None,
                  opt_sh=None, loss_sh=None):
        """Compile ``step_fn(params, opt_state, batch) -> (params,
        opt_state, loss)`` under the loop's donation contract.

        ``donate_argnums=(0, 1)`` donates the ``(params, opt_state)``
        input buffers: XLA aliases them to the outputs, making the step an
        in-place update rather than a 2x-peak-memory copy. When
        ``params_sh`` is given the mesh placement is pinned explicitly
        (``opt_sh``/``loss_sh`` pass through, ``None`` meaning "infer").
        """
        import jax

        # an operator sees a step recompiling: jit_compilations /
        # jit_compile_seconds / compile_cache_hits (docs/observability.md)
        _telemetry.arm_compile_counters()
        options = dict(donate_argnums=(0, 1))
        if params_sh is not None:
            options.update(in_shardings=(params_sh, opt_sh, batch_sh),
                           out_shardings=(params_sh, opt_sh, loss_sh))
        fn = jax.jit(step_fn, **options)
        fn._donate_argnums = (0, 1)
        fn._jit_args = (step_fn, options)   # hlo_scopes() builds it again
        return fn

    def _build_accuracy(self):
        """Jitted (correct_weighted, total_weight) over one batch; the
        reduction stays ON DEVICE so mesh-global batches spanning processes
        work (their per-row values are not host-addressable)."""
        import jax

        def acc_fn(params, batch):
            margin, label, weight = self._margin(params, batch)
            pred = self._pred_from_margin(margin)
            return ((pred == label) * weight).sum(), weight.sum()

        if self.mesh is None:
            return jax.jit(acc_fn)
        from jax.sharding import NamedSharding, PartitionSpec as P

        rep = NamedSharding(self.mesh, P())
        return jax.jit(acc_fn, out_shardings=(rep, rep))

    def step(self, batch):
        """One jitted update. Returns the loss as a DEVICE scalar — no
        host sync here; convert with :func:`host_scalar` when a float is
        actually needed."""
        if getattr(self, "_step_avals", None) is None:
            # hlo_scopes() compiles for these shapes; tracing's bookkeeping
            # never fails a training step
            try:
                self._step_avals = _abstract(
                    (self.params, self.opt_state, batch))
            except Exception:  # noqa: BLE001 - an exotic leaf: no scopes
                self._step_avals = ()
        with _telemetry.span("step_dispatch"):
            self.params, self.opt_state, loss = self._step(
                self.params, self.opt_state, batch)
        self._last_batch = batch    # walk_books() counts it, on demand
        return loss

    def walk_books(self) -> Dict[str, float]:
        """What the update's kernel walked for the batch the last
        :meth:`step` took (it is not donated: a reference is all the step
        keeps), counted on demand and outside any step by one small jitted
        function over the batch's ids, and set as the gauges
        ``walk_books{what=}`` (docs/observability.md):
        :func:`dmlc_tpu.ops.sorted_walk.walk_books` of the ids the update's
        walk sorts, with the ``real`` the step names. Where several walks
        share a step (a table dealt by rows: an owner walks what it
        received) every count is the mean over the chips and
        ``<what>_largest_chip`` the largest. Empty before the first step,
        and for a learner or a route whose update takes no kernel on the
        sorted walk."""
        import jax
        import numpy as np

        batch = getattr(self, "_last_batch", None)
        count = getattr(self, "_walk_books_of", None)
        if batch is None or count is None:
            return {}
        if getattr(self, "_walk_books_fn", None) is None:
            self._walk_books_fn = jax.jit(count)
        books = {}
        # (on demand, outside the loop: the one transfer is the caller's)
        for what, x in jax.device_get(self._walk_books_fn(batch)).items():
            books[what] = np.mean(x).item()
            if np.ndim(x):
                books[what + "_largest_chip"] = np.max(x).item()
        _telemetry.set_walk_books(books)
        return books

    def step_memory(self) -> Dict[str, int]:
        """What XLA's compile of the step says it holds a chip, in bytes:
        ``temp`` (the step's temporaries, which no allocator statistic of
        the running job tells from the tables), ``argument``, ``output``
        and ``alias`` (the donated state, counted in both), from the
        compile :meth:`hlo_scopes` makes (made here if it was not yet);
        also the gauges ``step_memory_bytes{kind=}``. Empty before the
        first :meth:`step`."""
        self.hlo_scopes()
        return dict(getattr(self, "_step_memory", None) or {})

    def hlo_scopes(self, program: str = "step") -> Dict[str, str]:
        """``{instruction name: op_name}`` of the compiled step, for the
        shapes of the first :meth:`step` call (empty before it): every
        instruction of every computation, ``""`` where XLA gave it no
        ``op_name``, so a reader can tell "no scope" from "not this
        program".

        A device trace names an operation by its HLO instruction
        (``fusion.3``), which XLA renumbers whenever the step changes; the
        ``op_name`` holds the ``jax.named_scope`` path the learner gave it
        (``jit(step)/fm_optimizer/...``, and ``transpose(jvp(fm_gather))``
        for the gradient's scatter), which does not. The names a running
        executable carries cannot be trusted for this: the persistent
        compilation cache leaves metadata out of its key, so a step that
        came from the cache may carry the names of whichever build first
        compiled it, and a profile shows those. So the step is lowered
        and compiled again here under a module name that holds a digest of
        this build's lowering with its names — a key of the persistent
        cache that only a build with the same names shares (a compile the
        first time a build asks, seconds; a cache hit after), and a new
        function, which none of JAX's in-memory caches can answer for
        with the running executable. No configuration is touched, so a
        compile on another thread is not disturbed. The instruction names
        are the running step's as long as XLA numbers the same program the
        same way under either module name; whoever joins them to a trace
        checks that every traced operation is found here. The same
        compile's ``memory_analysis()`` is kept for :meth:`step_memory`.

        ``program="ckpt_snapshot"``: the same map of the device copy a
        save dispatches (module ``jit_ckpt_snapshot``; every operation
        reads the scope ``ckpt_snapshot``), from the executable the first
        save compiled; empty before it."""
        import hashlib

        import jax

        if program == "ckpt_snapshot":
            plan = getattr(getattr(self, "_ckpt_books", None), "plan", None)
            compiled = getattr(plan, "_compiled", None)
            return _scopes_of_text(compiled.as_text()) if compiled else {}
        avals = getattr(self, "_step_avals", None)
        if not avals:
            return {}
        if getattr(self, "_hlo_scopes", None) is None:
            step_fn, options = self._step._jit_args

            def lowered(name):
                @functools.wraps(step_fn)   # its argument names name the
                def step(*args):            # parameter instructions
                    return step_fn(*args)

                step.__name__ = step.__qualname__ = name
                return jax.jit(step, **options).lower(*avals)

            digest = hashlib.sha256(lowered("step").as_text(
                debug_info=True).encode()).hexdigest()[:16]
            name = "step_scopes_" + digest
            compiled = lowered(name).compile()
            self._hlo_scopes = {
                ins: op.replace(f"jit({name})", "jit(step)")
                for ins, op in _scopes_of_text(compiled.as_text()).items()}
            sizes = compiled.memory_analysis()
            self._step_memory = {} if sizes is None else {
                kind: int(getattr(sizes, kind + "_size_in_bytes"))
                for kind in ("temp", "argument", "output", "alias")}
            _telemetry.set_step_memory(self._step_memory)
        return dict(self._hlo_scopes)

    # ---------------- save and resume (docs/checkpoint.md) ----------------

    def _checkpoint_spec(self):
        """The learner's one declaration of its state: a
        :class:`dmlc_tpu.models._checkpoint.CheckpointSpec`."""
        raise NotImplementedError(
            f"{type(self).__name__} declares no checkpoint state")

    def _checkpoint_adopt(self, tree) -> None:
        """Take a restored ``tree`` (shaped as the spec's) as the state."""
        self.params, self.opt_state = tree["params"], tree["opt_state"]

    def save_async(self, uri: str, step: int, device_iter=None,
                   keep_last: int = 2):
        """Start a checkpoint of the state after ``step`` steps (and of
        ``device_iter``'s position) into the directory ``uri``; returns a
        handle whose ``wait()`` returns once it is published and durable.
        Call it from the thread that dispatches steps, between two steps:
        a copy of the state is dispatched on the device behind the last
        step, and the steps dispatched after it never wait for the host.
        Refuses (:class:`~dmlc_tpu.models._checkpoint.CheckpointRefused`)
        where the device has no room for the copy. A save still in flight
        is waited for first. The store keeps the newest ``keep_last``."""
        from dmlc_tpu.models import _checkpoint

        return _checkpoint.begin_save(self, uri, step, device_iter,
                                      keep_last)

    def save(self, uri: str, step: int, device_iter=None,
             keep_last: int = 2):
        """:meth:`save_async` and ``wait()``: returns the published
        files' paths. Where no copy fits on the device the live arrays
        are read chunk by chunk: the caller is not stepping meanwhile."""
        from dmlc_tpu.models import _checkpoint

        try:
            handle = self.save_async(uri, step, device_iter, keep_last)
        except _checkpoint.CheckpointRefused:
            handle = _checkpoint.begin_save(
                self, uri, step, device_iter, keep_last, snapshot=False)
        return handle.wait()

    def restore(self, uri: str, device_iter=None) -> dict:
        """Take the state of the newest checkpoint under the directory
        ``uri`` (or of the checkpoint a file's path names) into this
        learner, built with the arguments of the one that saved — under
        whatever deal this one has — and ``device_iter`` to the saved
        position; ``{"step", "iterator", "paths"}``."""
        from dmlc_tpu.models import _checkpoint

        return _checkpoint.restore(self, uri, device_iter)

    @staticmethod
    def latest(uri: str):
        """``{"step", "paths"}`` of the newest whole checkpoint published
        under the directory ``uri``, or ``None``."""
        from dmlc_tpu.models import _checkpoint

        return _checkpoint.latest(uri)

    def checkpoint_stats(self) -> dict:
        """The checkpoint counters (docs/observability.md) and this
        learner's last save and restore by phase."""
        from dmlc_tpu.models import _checkpoint

        return _checkpoint.stats(self)

    def fit_epoch(self, device_iter, max_steps=None) -> Tuple[float, int]:
        """One pass over a DeviceIter; returns (mean loss, batches).
        ``max_steps`` is the SPMD step-count cap (module docstring).

        The per-step losses accumulate on device; the single
        :func:`host_scalar` call at the end of the pass is the epoch's
        only blocking device→host sync.
        """
        total, n = None, 0
        for batch in device_iter:
            loss = self.step(batch)
            total = loss if total is None else total + loss
            n += 1
            if max_steps is not None and n >= max_steps:
                break
        device_iter.reset()
        if n == 0:
            return 0.0, 0
        with _telemetry.span("epoch_sync"):
            mean = host_scalar(total) / n
        return mean, n

    def fit(self, device_iter, epochs: int = 1, log_fn=None,
            steps_per_epoch=None):
        for epoch in range(epochs):
            t0 = get_time()
            loss, nb = self.fit_epoch(device_iter, max_steps=steps_per_epoch)
            if log_fn:
                log_fn(epoch, loss, nb, get_time() - t0)
        return self

    def accuracy(self, device_iter, max_steps=None) -> float:
        """Weighted accuracy over one pass, reduced ON DEVICE (replicated
        scalars — pod-safe); ``max_steps`` as in :meth:`fit_epoch`. The
        partials stay on device; the two :func:`host_scalar` calls at the
        end are the pass's only syncs."""
        correct, total = None, None
        n = 0
        for batch in device_iter:
            c, t = self._accuracy(self.params, batch)
            correct = c if correct is None else correct + c
            total = t if total is None else total + t
            n += 1
            if max_steps is not None and n >= max_steps:
                break
        device_iter.reset()
        if n == 0:
            return 0.0
        return host_scalar(correct) / max(host_scalar(total), 1.0)
