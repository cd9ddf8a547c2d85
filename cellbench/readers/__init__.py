"""One module per kind of per-layer reading. Each has
``read(ctx, params) -> float | None``: ``params`` is the metric's own file
under ``cellbench/metrics/``, ``ctx`` what one run observed (see
``cellbench.run.Observed``). A reader that finds nothing to read returns
``None`` and the metric is left out of the line."""
