"""How evenly the batches' real slots fell on the chips that own their
rows: the largest chip's count over the mean, from the learner's own
books (``FFMLearner.shard_slots()``: every step so far, set-up included),
read after the window. 1 is an even deal; ``shards`` is one chip owning
every slot. No value where the learner keeps no such books (a table that
is not dealt, a parent commit)."""


def read(ctx, params):
    books = getattr(getattr(ctx.adapter, "learner", None), "shard_slots",
                    None)
    counts = books() if books else None
    if not counts or not sum(counts):
        return None
    return max(counts) * len(counts) / sum(counts)
