"""Median device time of one call of the served kernel, from the trace."""
import harness


def read(ctx):
    calls = harness.kernel_calls_us(ctx.trace)
    return harness.percentile(calls, 50) if calls else None
