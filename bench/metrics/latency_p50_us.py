"""Median latency, intended arrival to answer held, over every answered
event offered in the window."""
import harness


def read(ctx):
    return harness.percentile(ctx.latency_us, 50)
