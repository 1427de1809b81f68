"""99th percentile of t_start - t_submit: the collection window and any
backlog ahead of the event, from the program's request timestamps."""
import harness


def read(ctx):
    return harness.percentile(ctx.queue_wait_us, 99)
