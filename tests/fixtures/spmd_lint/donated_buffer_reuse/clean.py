"""CLEAN: donated buffers are rebound (the canonical train loop)."""
import jax

step = jax.jit(lambda p, b: p, donate_argnums=(0,))


def rebound(params, batch):
    params = step(params, batch)    # rebinding consumes the donation
    return params["w"].sum()


def rebound_loop(params, batches):
    for b in batches:
        params = step(params, b)    # fresh buffer every iteration
    return params


def exclusive_branches(params, batch, on_device):
    if on_device:
        out = step(params, batch)   # donates only on this path...
    else:
        out = params                # ...so this read can never race it
    return out


def pool_row_rebound(pool, batch):
    pool.caches = step(pool.caches, batch)  # attribute rebinding
    return pool.caches              # consumes the donation


def pool_method(pool, batch):
    # the cache pool's own face (serving/cache_pool.py::CachePool.update):
    # the launch is handed the buffers, donates them, and the pool binds
    # what comes back — no name outlives the call
    pool.update(lambda caches: (None, step(caches, batch)))
    return pool.caches
