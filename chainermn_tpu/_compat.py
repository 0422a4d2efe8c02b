"""Thin names over the one installed JAX.

The code is written for exactly the stack the sandbox and the chip
machine carry: jax/jaxlib 0.9.0, libtpu 0.0.34, flax 0.12.3, optax
0.2.6, Python 3.12.  Every API the package needs is native there
(``jax.shard_map`` with vma checking, ``jax.lax.axis_size``,
``jax.lax.pcast``, ``jax.typeof``, ``ShapeDtypeStruct(vma=)``,
``pltpu.CompilerParams``, a differentiable ``optimization_barrier``),
so this module holds no version branches — only the short names the
package and its tests import from one place.
"""

from __future__ import annotations

import jax
from jax import shard_map  # noqa: F401  (re-exported: 30+ internal imports)

axis_size = jax.lax.axis_size
typeof = jax.typeof
optimization_barrier = jax.lax.optimization_barrier


def pcast_varying(x, axis_names):
    """Promote a replicated value to varying over ``axis_names``."""
    return jax.lax.pcast(x, axis_names, to="varying")


def shape_dtype_struct(shape, dtype, vma=None):
    """``jax.ShapeDtypeStruct`` carrying the varying-mesh-axes type."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def all_gather_invariant(x, axis_name, *, axis: int = 0, tiled: bool = False):
    """All-gather whose result is typed replication-INVARIANT.

    Under 0.9.0's vma rules ``jax.lax.all_gather`` is varying → varying,
    so a value that has to leave ``shard_map`` through ``out_specs=P()``
    (the int8 ring's gathered gradient on its way to the optimizer)
    cannot end in it.  The varying → invariant form has the same
    lowering and the same wire bytes; 0.9.0 ships it but exports it
    under no public name, hence the one private import of the package.
    """
    from jax._src.lax.parallel import all_gather_invariant as gather
    return gather(x, axis_name, axis=axis, tiled=tiled)


def tpu_compiler_params(**kwargs):
    """``pltpu.CompilerParams`` — resolved lazily so importing this
    module never pulls Pallas in."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(**kwargs)
