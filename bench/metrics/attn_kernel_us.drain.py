"""Median device time of one call of the ParT attention kernel (the
``pallas_call`` the kind names, one call per particle block and batch),
from the trace."""
import harness


def read(ctx):
    calls = getattr(ctx.kind, "attn_calls", lambda *_: [])(ctx.config,
                                                           ctx.trace)
    return harness.percentile([us for _, us in calls], 50) if calls else None
