"""The ParT attention kernel's share of its roofline: over the window's
calls, the least time the chip could take, the larger of the kernel's
operations over the peak the kind names (``ctx.peak_ops``) and its bytes
over the HBM peak (both counted by the kind per jet, the jets read from
each call's shape), over the calls' device time."""


def read(ctx):
    calls = getattr(ctx.kind, "attn_calls", lambda *_: [])(ctx.config,
                                                           ctx.trace)
    if not calls or not ctx.peaks:
        return None
    ops, nbytes = ctx.kind.attn_ops(ctx.config), ctx.kind.attn_bytes(
        ctx.config)
    least_s = sum(max(jets * ops / ctx.peak_ops,
                      jets * nbytes / ctx.peaks["hbm_bytes_per_s"])
                  for jets, _ in calls)
    return 100.0 * least_s / (sum(us for _, us in calls) * 1e-6)
