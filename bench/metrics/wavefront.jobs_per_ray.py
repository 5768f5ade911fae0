"""Datapath jobs per ray: the box-test and triangle jobs that the result
records count, over the rays of every call in the window."""
import jax.numpy as jnp


def read(ctx):
    calls = ctx.window.calls
    jobs = sum(int(jnp.sum(c.result.quadbox_jobs))
               + int(jnp.sum(c.result.triangle_jobs)) for c in calls)
    return jobs / sum(c.rows for c in calls)
