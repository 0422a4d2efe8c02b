"""The trained expert layer's row moves AS THEY WERE before ISSUE 39 — the
reference of ``tests/test_moe_rows.py`` and of the expert layer's compile in
``tests/test_chip_compile.py``, not a copy of anything the program still
runs: every row-side gather takes all ``M`` rows of the rows' buffer (a dead
row reads ``x[0]``), and ``d_gates`` gathers ``rows`` once more a choice,
eight ``(T, D)`` gathers a layer."""

import jax
import jax.numpy as jnp

from chainermn_tpu.parallel import moe as moe_mod

_weighted_rows = moe_mod._weighted_rows      # the token side: not ISSUE 39's


@jax.custom_vjp
def gather_rows(x, row_token, dest, is_held):
    return jnp.take(x, row_token, axis=0)


def _gather_rows_fwd(x, row_token, dest, is_held):
    return jnp.take(x, row_token, axis=0), (dest, is_held)


def _gather_rows_bwd(res, d_rows):
    dest, is_held = res
    return _weighted_rows(d_rows, None, dest, is_held, mode="clip").astype(
        d_rows.dtype), None, None, None


gather_rows.defvjp(_gather_rows_fwd, _gather_rows_bwd)


@jax.custom_vjp
def combine(rows, gates, dest, is_held, row_token):
    return _weighted_rows(rows, gates, dest, is_held)


def _combine_fwd(rows, gates, dest, is_held, row_token):
    return (_weighted_rows(rows, gates, dest, is_held),
            (rows, gates, dest, is_held, row_token))


def _combine_bwd(res, dy):
    rows, gates, dest, is_held, row_token = res
    m = rows.shape[0]
    row_gate = jnp.zeros((m,), jnp.float32).at[
        jnp.where(is_held, dest, m).reshape(-1)].set(
            gates.reshape(-1), mode="drop")
    d_rows = (jnp.take(dy, row_token, axis=0, mode="clip")
              * row_gate[:, None]).astype(rows.dtype)
    d_gates = jnp.stack([
        jnp.where(is_held[:, j],
                  (jnp.take(rows, dest[:, j], axis=0, mode="clip").astype(
                      jnp.float32) * dy).sum(-1), 0.0)
        for j in range(dest.shape[1])], axis=1)
    return d_rows, d_gates.astype(gates.dtype), None, None, None


combine.defvjp(_combine_fwd, _combine_bwd)


def install(monkeypatch) -> None:
    """Make ``parallel/moe.py`` move its rows the parent's way (the
    gathers' mode, the live row count and the chunk are dropped)."""
    monkeypatch.setattr(
        moe_mod, "_gather_rows",
        lambda x, row_token, dest, is_held, mode:
        gather_rows(x, row_token, dest, is_held))
    monkeypatch.setattr(
        moe_mod, "_combine",
        lambda rows, gates, dest, is_held, row_token, n_live, chunk:
        combine(rows, gates, dest, is_held, row_token))
