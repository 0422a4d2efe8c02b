"""A gated delta-rule linear-attention layer (Kimi Delta Attention; Kimi
Linear, arXiv 2510.26692), in the two forms serving needs.

Per head the layer keeps a ``(d_k, d_v)`` float32 state ``S`` and moves it
one token at a time::

    S' = Diag(exp(g_t)) S_{t-1}            g_t <= 0, one decay a CHANNEL
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

``q, k, v`` come out of one fused projection, a causal depthwise
convolution over time (width ``conv_width``) and SiLU; ``q`` and ``k`` are
L2-normalised per head (``q`` times ``d_k^-1/2``); the decay is
``-exp(A_log) * softplus(W_f h + dt_bias)``, ``beta = sigmoid(w_b h)``; the
read-out is RMS-normalised per head and gated by ``sigmoid(W_g h)`` before
the output projection.  What a sequence keeps a layer is ``S`` and the last
``conv_width - 1`` rows of the fused projection — a fixed size, whatever
its length (``blocks.cache_layout`` declares both as STATE buffers).

* :func:`kda_chunked` is the PREFILL form, derived from the recurrence and
  not a second definition: chunks of ``chunk`` tokens, inside a chunk the
  pseudo-values ``w_i = beta_i (v_i - S_{i-1}'^T k_i)`` solve one
  unit-lower-triangular system (the WY / UT form) whose right-hand side is
  linear in the chunk's start state, and the state is carried across
  chunks by a ``lax.scan``.  Plain XLA: matmuls, one batched triangular
  solve and a scan of ``S / chunk`` steps.
* the one-token TICK form is ``ops/kda_step.py`` (a Pallas kernel that
  touches the busy slots' state only, in place) behind ``ops/conv_step.py``
  (the convolution window one token on, in place, over the blocks of slots
  that hold a busy one), each with its plain twin for other backends:
  between the fused projection and the state kernel a tick materialises no
  temporary of the pool's size.

With a decay per channel the factor between two positions,
``exp(G_i - G_j)`` (``G`` the running sum of ``g``), cannot be split into
``exp(G_i) * exp(-G_j)`` over a chunk: the second overflows float32 after
a few dozen fast-decaying tokens.  Every exponent taken here is a
DIFFERENCE that is ``<= 0``: inside a sub-chunk of 16 the factors are
formed pair by pair, and between sub-chunks they are split at the later
sub-chunk's start, where both halves are ``<= 0``.  Underflow to 0 is the
true value to float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .blocks import _dense, rms_norm

HIGHEST = jax.lax.Precision.HIGHEST
_SUB = 16


def _short_conv(window, mixed, weight, n_real):
    """Causal depthwise convolution of ``mixed (B, S, C)`` with ``weight
    (W, C)`` (``weight[-1]`` meets the current token), the ``W - 1`` rows
    before position 0 given by ``window (B, W-1, C)``.  Returns the
    float32 result and the window after the ``n_real (B,)`` leading rows
    (the last ``W - 1`` of them, reaching into ``window`` for a short
    sequence)."""
    w = weight.shape[0]
    s = mixed.shape[1]
    xs = jnp.concatenate([window.astype(mixed.dtype), mixed], axis=1)
    wf = weight.astype(jnp.float32)
    y = sum(xs[:, i:i + s].astype(jnp.float32) * wf[i] for i in range(w))
    new_window = jax.vmap(lambda x, n: jax.lax.dynamic_slice_in_dim(
        x, n, w - 1, axis=0))(xs, n_real)
    return y, new_window


def _busy(live, b: int):
    """``(B,) bool`` of a tick's rows that carry a token (None: all)."""
    return jnp.ones((b,), bool) if live is None else live[:, 0]


def kda_project(cfg, h, a, window, live):
    """Everything the recurrence takes, from normed ``h (B, S, D)``:
    ``q, k (B, S, H, d_k)`` float32 normalised, ``v (B, S, H, d_v)``,
    the log-decay ``g (B, S, H, d_k) <= 0``, ``beta (B, S, H)``, the
    output gate ``(B, S, H·d_v)`` and the convolution window after the
    live rows.  ``live (B, S) bool`` (None: all): a row that carries no
    token takes ``beta = 0, g = 0`` (it leaves the state as it is) and
    is not in the window.  ``S == 1`` is the tick: the window moves
    through ``ops/conv_step``, a row that carries no token keeps its
    window bit for bit and reads ``q = k = v = 0``."""
    b, s, _ = h.shape
    nh, dk, dv, r = cfg.n_heads, cfg.head_dim, cfg.head_dim, cfg.gate_rank
    with jax.named_scope("conv"):
        mixed = _dense(h, a["wqkv"])
        if s == 1:
            from ..ops import conv_step as cs
            step = cs.conv_step if jax.default_backend() == "tpu" \
                and cs.fits(window) else cs.conv_step_xla
            y, window = step(window, mixed, a["conv"], _busy(live, b))
        else:
            n_real = (jnp.full((b,), s, jnp.int32) if live is None
                      else live.sum(-1).astype(jnp.int32))
            y, window = _short_conv(window, mixed, a["conv"], n_real)
        y = jax.nn.silu(y)
        q, k, v = (y[..., i * nh * dk:(i + 1) * nh * dk].reshape(
            b, s, nh, -1) for i in range(3))
        unit = lambda x: x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True)
                                           + 1e-6)
        q, k = unit(q) * dk ** -0.5, unit(k)
    with jax.named_scope("gate"):
        low = _dense(h, a["w_low"])          # [decay | out gate | beta]
        f = _dense(low[..., :r], a["wf_up"]).astype(jnp.float32)
        g = -jnp.exp(a["a_log"].astype(jnp.float32))[:, None] * \
            jax.nn.softplus(f + a["dt_bias"].astype(jnp.float32)
                            ).reshape(b, s, nh, dk)
        beta = jax.nn.sigmoid(low[..., 2 * r:].astype(jnp.float32))
        gate = jax.nn.sigmoid(_dense(low[..., r:2 * r], a["wg_up"]
                                     ).astype(jnp.float32))
        if live is not None:
            g = jnp.where(live[..., None, None], g, 0.0)
            beta = jnp.where(live[..., None], beta, 0.0)
    return q, k, v, g, beta, gate, window


def kda_output(cfg, o, gate, a, eps: float, dtype):
    """``W_o [RMSNorm_head(o) * gate]`` of the read-out ``o (B, S, H,
    d_v)`` float32."""
    b, s = o.shape[:2]
    y = (rms_norm(o, a["o_norm"], eps).reshape(b, s, -1) * gate
         ).astype(dtype)
    return _dense(y, a["wo"])


def _pair_factors(x, k, g_cum):
    """``M[i, j] = sum_c x_i[c] k_j[c] exp(G_i[c] - G_j[c])`` for ``j <=
    i`` inside one chunk (0 above the diagonal); ``x, k, g_cum (..., C,
    d_k)``.  Every exponent is a difference that is ``<= 0``."""
    c = x.shape[-2]
    rows = []
    for lo in range(0, c, _SUB):
        hi = min(lo + _SUB, c)
        g_a, x_a, k_a = g_cum[..., lo:hi, :], x[..., lo:hi, :], k[..., lo:hi, :]
        # inside the sub-chunk: pair by pair
        diff = g_a[..., :, None, :] - g_a[..., None, :, :]
        tri = jnp.tril(jnp.ones((hi - lo, hi - lo), bool))
        e = jnp.exp(jnp.where(tri[..., None], diff, -jnp.inf))
        blocks = [(x_a[..., :, None, :] * k_a[..., None, :, :] * e).sum(-1)]
        if lo:
            # against the earlier sub-chunks: split at this one's start
            ref = g_cum[..., lo - 1:lo, :]
            left = x_a * jnp.exp(g_a - ref)
            right = k[..., :lo, :] * jnp.exp(ref - g_cum[..., :lo, :])
            blocks.insert(0, jnp.einsum("...ic,...jc->...ij", left, right,
                                        precision=HIGHEST))
        row = jnp.concatenate(blocks, -1)
        rows.append(jnp.pad(row, [(0, 0)] * (row.ndim - 1) + [(0, c - hi)]))
    return jnp.concatenate(rows, -2)


def kda_chunked(q, k, v, g, beta, state, chunk: int = 64):
    """The recurrence over ``S`` tokens from ``state (B, H, d_k, d_v)``,
    chunk by chunk: ``(o (B, S, H, d_v), state after the last token)``.
    ``q, k, g (B, S, H, d_k)``, ``v (B, S, H, d_v)``, ``beta (B, S, H)``,
    all float32; a token with ``beta = 0, g = 0`` leaves the state as it
    is, which is how the sequence is padded to whole chunks."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    pad = -s % chunk
    if pad:
        q, k, v, g = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
                      for x in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    n = (s + pad) // chunk
    # (chunks, B, H, C, d): the scan runs over the leading axis
    split = lambda x: jnp.moveaxis(
        x.reshape(b, n, chunk, h, -1), (1, 3), (0, 2))
    q, k, v, g = (split(x.astype(jnp.float32)) for x in (q, k, v, g))
    beta = split(beta.astype(jnp.float32)[..., None])         # (..., C, 1)
    g_cum = jnp.cumsum(g, axis=-2)
    strict = jnp.tril(jnp.ones((chunk, chunk), jnp.float32), -1)
    # (I + beta A) W = beta (V - K_dec S_0): solved once for both parts of
    # the right-hand side, so that W = U - K_w S_0 inside the scan
    lower = jnp.eye(chunk) + beta * _pair_factors(k, k, g_cum) * strict
    rhs = beta * jnp.concatenate([v, k * jnp.exp(g_cum)], -1)
    sol = jax.lax.linalg.triangular_solve(
        lower, rhs, left_side=True, lower=True, unit_diagonal=True)
    u, k_w = sol[..., :dv], sol[..., dv:]
    q_dec = q * jnp.exp(g_cum)
    within = _pair_factors(q, k, g_cum)          # o's part inside the chunk
    g_end = g_cum[..., -1:, :]
    k_end = k * jnp.exp(g_end - g_cum)
    decay_end = jnp.exp(jnp.swapaxes(g_end, -1, -2))          # (..., dk, 1)

    def step(s0, xs):
        u_c, k_w_c, q_c, within_c, k_end_c, decay_c = xs
        mm = lambda spec, x, y: jnp.einsum(spec, x, y, precision=HIGHEST)
        w = u_c - mm("bhck,bhkv->bhcv", k_w_c, s0)
        o = mm("bhck,bhkv->bhcv", q_c, s0) + mm("bhcj,bhjv->bhcv",
                                                within_c, w)
        return decay_c * s0 + mm("bhck,bhcv->bhkv", k_end_c, w), o

    state, o = jax.lax.scan(step, state.astype(jnp.float32),
                            (u, k_w, q_dec, within, k_end, decay_end))
    o = jnp.moveaxis(o, (0, 2), (1, 3)).reshape(b, s + pad, h, dv)
    return o[:, :s], state


def kda_layer(cfg, h, a, state, window, live, eps: float, slots=None):
    """The layer on normed ``h (B, S, D)`` from ``(state (B, H, d_k, d_v)
    float32, window (B, W-1, 3·H·d))``: ``(y (B, S, D), state, window)``
    after the live rows.  ``S == 1`` is the tick — every ``live (B, 1)``
    row moves one token on through ``ops/conv_step`` and ``ops/kda_step``
    (``slots``: the tick's busy list), the others keep state and window
    bit for bit — and ``S > 1`` the chunked form."""
    b, s, _ = h.shape
    q, k, v, g, beta, gate, new_window = kda_project(cfg, h, a, window,
                                                     live)
    with jax.named_scope("state_update"):
        if s == 1:
            from ..ops.kda_step import kda_step, kda_step_xla
            one = (q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], state,
                   _busy(live, b))
            o, state = kda_step(*one, slots) \
                if jax.default_backend() == "tpu" else kda_step_xla(*one)
            o = o[:, None]
        else:
            o, state = kda_chunked(q, k, v, g, beta, state, cfg.chunk)
    with jax.named_scope("proj"):       # head norm, output gate, W_o
        return (kda_output(cfg, o, gate, a, eps, h.dtype), state,
                new_window.astype(window.dtype))
