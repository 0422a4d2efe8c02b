"""BAD: buffers read after being donated to a jitted call."""
import jax

step = jax.jit(lambda p, b: p, donate_argnums=(0,))


def read_after_donation(params, batch):
    out = step(params, batch)       # params' buffer is DONATED here
    norm = params["w"].sum()        # ...and read again: may alias out
    return out, norm


def stale_loop_reuse(params, batches):
    for b in batches:
        _ = step(params, b)         # donated on iteration 1, reused on 2
    return params


def cache_pool_attribute(pool, batch):
    out = step(pool.caches, batch)  # the serving cache-pool hazard:
    return out, pool.caches         # pool row donated, then read


def pool_method_launch_reads_back(pool, batch):
    def launch(caches):
        new = step(caches, batch)   # the pool's buffers donated...
        return caches[0].sum(), new  # ...and read inside the launch
    return pool.update(launch)
