"""Median time of one FleetServer.offer call, on the benchmark clock."""
import harness


def read(ctx):
    return harness.percentile(ctx.offer_us, 50)
