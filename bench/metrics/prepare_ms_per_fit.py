"""``prepare_ms_per_fit`` (entry layer: ``Workload.bind`` -> the
workload's ``prepare``: quantization, ``shard_rows`` placement, the
sigmoid table, k-means' initial draw): host milliseconds a fit spends
inside the program's ``pim.prepare`` spans in the window."""

from bench import spans


def read(ctx):
    return spans.ms_per_fit(ctx, spans.PREPARE)
