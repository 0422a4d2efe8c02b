"""The selective scan of a Mamba-1 layer over a whole prompt.

The recurrence ``ops/ssm_step.py`` moves one token on, run over ``S``
tokens from a start state::

    s_t = exp(dt_t * A) * s_{t-1} + (dt_t * c_t) B_t^T     s: (N, E) float32
    y_t = C_t . s_t + D * c_t

The plain forms of it in XLA — the elementwise one and the associative
scan — write ``exp(dt A)`` and ``dt c B`` for every token, ``(S, E, N)``
float32 each (335 MB a sequence a layer at S 1024, E 5120, N 16), several
passes each.  Here no such array exists: a grid step holds one block of
channels' state in VMEM as float32 and walks a chunk of tokens in a loop,
reading ``c`` and ``dt`` a token at a time and writing ``y``; the chunks of
a sequence follow each other on the grid's last axis and hand the state on
in a VMEM scratch.  What reaches HBM is ``c``, ``dt``, ``y`` (``(S, E)``
each), ``B`` and ``C`` (``(S, N)``) and the state before and after.

The layout is ``ops/ssm_step.py``'s: channels as ``(E / L, L)`` slabs, a
block of 8 rows of them (a whole float32 register a state index) a grid
step, ``B_t[n]`` and ``C_t[n]`` scalars from scalar memory, every
operation elementwise.  A token with ``dt = 0`` leaves the state as it is
(``exp(0) = 1``, ``0 * c B = 0``), which is how a padded prompt is scanned:
the caller zeroes ``dt`` at and after ``n_real``, and the state handed back
is the state after the last real token.  Chunks that lie wholly at or after
``n_real`` are not walked at all.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .._compat import shape_dtype_struct as _sds
from .kv_cache import _inherit_vma
from .ssm_step import scalars, slabs

__all__ = ["selective_scan", "selective_scan_xla", "walked"]

_CHUNK = 128      # tokens a grid step walks
_ROWS = 8         # rows of L channels a grid step holds: one register


def _chunk(seq_len: int) -> int:
    return _CHUNK if seq_len % _CHUNK == 0 else seq_len


def walked(n_real: int, seq_len: int) -> int:
    """Tokens the kernel walks of a ``seq_len``-token prompt whose first
    ``n_real`` are real: whole chunks, up to the one that holds the last
    real token (host arithmetic, for the engine's counters)."""
    chunk = _chunk(seq_len)
    return min(-(-int(n_real) // chunk) * chunk, seq_len)


def selective_scan_xla(c, dt, b, cc, a, d, state):
    """The recurrence token by token in plain ``jax.numpy`` (other
    backends, and the kernel's oracle; a ``lax.scan`` whose carry is the
    state, so no ``(S, E, N)`` array here either): ``c, dt (B, S, E)``,
    ``b, cc (B, S, N)``, ``a (N, E)`` (the NEGATIVE rates, ``-exp(A_log)``),
    ``d (E,)``, ``state (B, N, E / L, L)`` float32.  Returns ``(y (B, S, E)
    float32, state after the last token)``."""
    shape = state.shape
    f32 = jnp.float32
    a, d = a.astype(f32), d.astype(f32)

    def step(s, x):
        c_t, dt_t, b_t, c_out = x
        s = jnp.exp(dt_t[:, None, :] * a) * s \
            + (dt_t * c_t)[:, None, :] * b_t[:, :, None]
        return s, (s * c_out[:, :, None]).sum(1) + d * c_t

    time_major = lambda v: jnp.moveaxis(v.astype(f32), 1, 0)
    s, y = jax.lax.scan(step, state.reshape(shape[0], shape[1], -1),
                        tuple(time_major(v) for v in (c, dt, b, cc)))
    return jnp.moveaxis(y, 0, 1), s.reshape(shape)


def _kernel(n_real_ref, bc_ref, c_ref, dt_ref, a_ref, d_ref, s0_ref,
            y_ref, so_ref, s_scr, *, seq_len):
    n_state = s_scr.shape[0]
    chunk = c_ref.shape[1]
    seq, k = pl.program_id(0), pl.program_id(2)

    @pl.when(k == 0)
    def _start():
        s_scr[...] = s0_ref[0]

    first = k * chunk                         # the chunk's first token

    @pl.when(first < n_real_ref[seq])
    def _walk():
        a = [a_ref[n] for n in range(n_state)]
        d = d_ref[...]
        row0 = (seq * seq_len + first) * (2 * n_state)

        def token(t, s):
            c, dt = c_ref[0, t], dt_ref[0, t]
            dtc = dt * c
            y = d * c
            at = row0 + t * (2 * n_state)     # the token's [B | C] scalars
            new = []
            for n in range(n_state):
                s_n = jnp.exp(dt * a[n]) * s[n] + dtc * bc_ref[at + n]
                y = y + s_n * bc_ref[at + n_state + n]
                new.append(s_n)
            y_ref[0, t] = y
            return tuple(new)

        s = jax.lax.fori_loop(0, chunk, token,
                              tuple(s_scr[n] for n in range(n_state)))
        for n in range(n_state):
            s_scr[n] = s[n]

    @pl.when(first >= n_real_ref[seq])
    def _padding():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(k == pl.num_programs(2) - 1)
    def _finish():
        so_ref[0] = s_scr[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def selective_scan(c, dt, b, cc, a, d, state, n_real=None, *,
                   interpret: bool = False):
    """:func:`selective_scan_xla` as one Pallas pass, the state of a block
    of channels resident in VMEM.  Shapes as there; ``n_real (B,) int32``:
    the leading tokens that are real (None: all) — the caller has zeroed
    ``dt`` from there on, and the chunks from there on are skipped (their
    ``y`` reads 0)."""
    bsz, seq_len, _ = c.shape
    _, n_state, rows, lane = state.shape
    f32 = jnp.float32
    chunk = _chunk(seq_len)
    rb = _ROWS if rows % _ROWS == 0 else rows
    if n_real is None:
        n_real = jnp.full((bsz,), seq_len, jnp.int32)
    slab = functools.partial(slabs, rows=rows, lane=lane)

    tokens = pl.BlockSpec((1, chunk, rb, lane),
                          lambda i, e, k, nr, bc: (i, k, e, 0))
    whole = pl.BlockSpec((1, n_state, rb, lane),
                         lambda i, e, k, nr, bc: (i, 0, e, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bsz, rows // rb, seq_len // chunk),
        in_specs=[
            tokens, tokens,
            pl.BlockSpec((n_state, rb, lane),
                         lambda i, e, k, nr, bc: (0, e, 0)),
            pl.BlockSpec((rb, lane), lambda i, e, k, nr, bc: (e, 0)),
            whole,
        ],
        out_specs=[tokens, whole],
        scratch_shapes=[pltpu.VMEM((n_state, rb, lane), f32)])
    vma = _inherit_vma(c, state)
    y, new_state = pl.pallas_call(
        functools.partial(_kernel, seq_len=seq_len),
        grid_spec=grid_spec,
        out_shape=[_sds((bsz, seq_len, rows, lane), f32, vma=vma),
                   _sds(state.shape, f32, vma=vma)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="selective_scan",
        interpret=interpret,
    )(n_real.astype(jnp.int32), scalars(b, cc), slab(c), slab(dt), slab(a),
      slab(d), state.astype(f32))
    return y.reshape(bsz, seq_len, -1), new_state
