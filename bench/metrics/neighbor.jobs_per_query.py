"""Datapath jobs per query: the point-box and point-distance jobs that the
result records count (``NearestResult.box_jobs`` and ``point_jobs`` on the
tree route), over the queries of every call in the window.  A program
whose records carry no job counts gives no reading."""
import jax.numpy as jnp


def read(ctx):
    calls = ctx.window.calls
    jobs = 0
    for c in calls:
        box = getattr(c.result, "box_jobs", None)
        point = getattr(c.result, "point_jobs", None)
        if box is None or point is None:
            return None
        jobs += int(jnp.sum(box)) + int(jnp.sum(point))
    return jobs / sum(c.rows for c in calls)
