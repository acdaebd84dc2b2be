"""Share of the edge store's non-empty blocks that skip() kept off the
superstep's schedule, over the window: the sum of
``SuperstepRecord.blocks_skipped`` over the sum of ``blocks_skipped`` +
``blocks_read`` + ``cache_hits`` (each superstep's non-empty blocks are
skipped, read off disk or served from the hot cache). Nothing for a plan
that keeps its edges in memory."""


def read(run):
    if run.plan_mode != "streamed" or not run.window:
        return None
    skipped = sum(r.blocks_skipped for r in run.window)
    total = skipped + sum(r.blocks_read + r.cache_hits for r in run.window)
    if total == 0:
        return None
    return skipped / total
