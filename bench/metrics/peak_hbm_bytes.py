"""``peak_hbm_bytes`` (placement: ``PimGrid.shard_rows`` and the
programs' temporaries): the largest ``peak_bytes_in_use`` over the
cell's chips after the window, as the runtime's allocator counts it."""


def read(ctx):
    return ctx.memory_peak_bytes or None
