"""The whole served step's share of the chip's peak for the kind's
operations (``ctx.peak_ops``): events answered in the window times the
operations an event needs, over the window and the peak."""


def read(ctx):
    if not ctx.peaks or not ctx.answered_in_window:
        return None
    ops = ctx.answered_in_window * ctx.ops_per_event
    return 100.0 * ops / ctx.window_s / ctx.peak_ops
