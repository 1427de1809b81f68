"""Median worker step per served batch, t_done - t_start: stack, copy to
the device, dispatch, kernel, copy back."""
import harness


def read(ctx):
    return harness.percentile(ctx.service_us, 50)
