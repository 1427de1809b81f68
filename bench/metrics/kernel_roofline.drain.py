"""The served kernel's share of its roofline: the least time the chip
could take for the window's calls, the larger of int8 operations over the
int8 peak and bytes over the HBM peak, over the kernel's device time."""
import harness


def read(ctx):
    calls = harness.kernel_calls_us(ctx.trace)
    if not calls or not ctx.peaks or not ctx.batch_sizes:
        return None
    p = ctx.peaks
    least_s = sum(max(b * ctx.ops_per_event / p["int8_ops"],
                      ctx.min_bytes(b) / p["hbm_bytes_per_s"])
                  for b in ctx.batch_sizes)
    # One kernel call serves one batch; scale to the calls the trace saw.
    least_s *= len(calls) / len(ctx.batch_sizes)
    return 100.0 * least_s / (sum(calls) * 1e-6)
