"""Mean user rows per engine call of the server, over the window: the
change in ``ServerStats`` between the window's open and its end."""


def read(ctx):
    before, after = ctx.server_stats()
    batches = after.batches - before.batches
    if not batches:
        return None
    rows = (after.mean_batch_rows * after.batches
            - before.mean_batch_rows * before.batches)
    return rows / batches
