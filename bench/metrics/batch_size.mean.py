"""Mean batch the server formed for the window's events
(FleetServer.stats(name).batch_sizes)."""
import numpy as np


def read(ctx):
    return float(np.mean(ctx.batch_sizes)) if ctx.batch_sizes else None
