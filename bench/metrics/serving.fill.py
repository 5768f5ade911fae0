"""User rows over the padded rows that the server's engine calls ran, over
the window, in percent (``ServerStats``, as for ``serving.batch_rows``)."""


def read(ctx):
    before, after = ctx.server_stats()

    def rows(s):
        return s.mean_batch_rows * s.batches

    def padded(s):
        return rows(s) / s.mean_fill if s.mean_fill else 0.0

    pad = padded(after) - padded(before)
    return 100.0 * (rows(after) - rows(before)) / pad if pad else None
