"""One number of the checkpoint cell's own account
(``cellbench/learners/ffm_ckpt.py``: ``Adapter.ckpt_books()``), which the
adapter reads from the program's spans and ``checkpoint_stats()``:

``stall_ms``        the ``ckpt_snapshot`` span of the window's save on
                    the dispatching thread: what a save holds it for
``drain_gb_per_s``  the window's save: payload bytes over the seconds from
                    the first transfer to the host asked for to the last
                    chunk landed there (``last_save["landed_s"]``; the
                    writes between them included): the rate at which the
                    state leaves the device. The ``ckpt_drain`` spans are
                    the saver's waits for chunks asked for earlier, and
                    no divisor: a slower disk makes them shorter
``publish_s``       the window's save, from the call to published
``restore_s``       set-up's restore of the learner the harness steps
``setup_save_s``    set-up's save of the start, from the call to published

No value where the adapter keeps no such account (another learner)."""


def read(ctx, params):
    books = getattr(ctx.adapter, "ckpt_books", None)
    return None if books is None else books().get(params["key"])
