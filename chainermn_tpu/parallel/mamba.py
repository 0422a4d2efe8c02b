"""A Mamba-1 selective state-space layer (Gu & Dao 2023, arXiv 2312.00752;
as Jamba's mixer has it, arXiv 2403.19887: RMSNorms on the step, ``B`` and
``C``), in the two forms serving needs.

Per sequence the layer keeps an ``(N, E)`` float32 state ``s`` — ``E =
expand * D`` inner channels, ``N`` states a channel — and moves it one token
at a time by an INPUT-DEPENDENT discretisation::

    [u_t ; z_t]        = W_in h_t
    c_t                = SiLU(b_conv + sum_j w_conv[j] * u_{t-W+1+j})
    [dt_t; B_t; C_t]   = W_x c_t,  each RMS-normalised (learned scales)
    Dt_t               = softplus(W_dt dt_t + b_dt)              (E,)
    s_t                = exp(Dt_t A) * s_{t-1} + (Dt_t * c_t) B_t^T
    y_t                = C_t . s_t + D * c_t,     A = -exp(A_log)
    out_t              = W_out (y_t * SiLU(z_t))

No heads, no keys, no solve.  What a sequence keeps a layer is ``s`` and the
last ``conv_width - 1`` rows of ``u`` — a fixed size, whatever its length
(``blocks.cache_layout`` declares both as STATE buffers, the state in the
kernels' ``(N, E / L, L)`` layout).

* the PREFILL form is ``ops/selective_scan.py``: one pass over the prompt
  with the state of a block of channels resident in VMEM, no ``(S, E, N)``
  array anywhere; a row that carries no token has ``Dt = 0`` (it leaves the
  state as it is) and is not in the window, so after a padded prompt both
  stand at its last real token;
* the one-token TICK form is ``ops/ssm_step.py`` (the busy slots' state
  alone, in place, over the tick's busy list).

Other backends take the kernels' plain twins.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .blocks import _dense, rms_norm
from .kda import _short_conv


def mamba_project(cfg, h, a, window, live, eps: float):
    """Everything the recurrence takes, from normed ``h (B, S, D)``: ``c,
    dt (B, S, E)`` float32, ``B, C (B, S, N)`` float32, the gate input ``z
    (B, S, E)``, the convolution window after the live rows and their count
    ``n_real (B,)``.  ``live (B, S) bool`` (None: all): a row that carries
    no token takes ``dt = 0`` and is not in the window."""
    b, s, _ = h.shape
    e, n, r = cfg.d_inner, cfg.d_state, cfg.dt_rank
    f32 = jnp.float32
    with jax.named_scope("proj"):
        uz = _dense(h, a["w_in"])
        u, z = uz[..., :e], uz[..., e:]
    with jax.named_scope("conv"):
        n_real = (jnp.full((b,), s, jnp.int32) if live is None
                  else live.sum(-1).astype(jnp.int32))
        y, window = _short_conv(window, u, a["conv"], n_real)
        c = jax.nn.silu(y + a["conv_bias"].astype(f32))
    with jax.named_scope("proj"):
        low = _dense(c.astype(h.dtype), a["w_x"])       # [dt | B | C]
        step = rms_norm(low[..., :r], a["dt_norm"], eps)
        bm = rms_norm(low[..., r:r + n], a["b_norm"], eps).astype(f32)
        cm = rms_norm(low[..., r + n:], a["c_norm"], eps).astype(f32)
        dt = jax.nn.softplus(_dense(step, a["w_dt"]).astype(f32)
                             + a["dt_bias"].astype(f32))
        if live is not None:
            dt = jnp.where(live[..., None], dt, 0.0)
    return c, dt, bm, cm, z, window, n_real


def mamba_layer(cfg, h, a, state, window, live, eps: float, slots=None):
    """The layer on normed ``h (B, S, D)`` from ``(state (B, N, E / L, L)
    float32, window (B, W-1, E))``: ``(y (B, S, D), state, window)`` after
    the live rows.  ``S == 1`` is the tick — every ``live (B, 1)`` row moves
    one token on through ``ops/ssm_step`` (``slots``: the tick's busy list),
    the others keep state and window bit for bit — and ``S > 1`` the
    selective scan from the state given."""
    from ..ops.selective_scan import selective_scan, selective_scan_xla
    from ..ops.ssm_step import ssm_step, ssm_step_xla

    b, s, _ = h.shape
    c, dt, bm, cm, z, new_window, n_real = mamba_project(
        cfg, h, a, window, live, eps)
    on_tpu = jax.default_backend() == "tpu"
    with jax.named_scope("core"):
        rate = -jnp.exp(a["a_log"].astype(jnp.float32))          # (N, E)
        if s == 1:
            busy = jnp.ones((b,), bool) if live is None else live[:, 0]
            one = (c[:, 0], dt[:, 0], bm[:, 0], cm[:, 0], rate, a["d"],
                   state, busy)
            y, state = ssm_step(*one, slots) if on_tpu \
                else ssm_step_xla(*one)
            y = y[:, None]
        elif on_tpu:
            y, state = selective_scan(c, dt, bm, cm, rate, a["d"], state,
                                      n_real)
        else:
            y, state = selective_scan_xla(c, dt, bm, cm, rate, a["d"], state)
    with jax.named_scope("proj"):       # the gate, W_out
        gated = (y * jax.nn.silu(z.astype(jnp.float32))).astype(h.dtype)
        return (_dense(gated, a["w_out"]), state,
                new_window.astype(window.dtype))
