"""99th percentile latency, intended arrival to answer held, over every
answered event offered in the window. Read per layer: the host's pauses
(about a tenth of a second, some 1% of the time) decide it."""
import harness


def read(ctx):
    return harness.percentile(ctx.latency_us, 99)
