"""The trained expert layer's row moves follow the work list (ISSUE 39):
``parallel/moe.py``'s backward row-side pass walks the live chunks of the
rows' buffer and ``d_gates`` is a row-wise dot read back as scalars —
against the parent's formulation (``tests/moe_rows_parent.py``: the pass
over all ``M`` rows, eight row gathers a layer for ``d_gates``), the grouped
products interpreted on the CPU.  Tiny sizes are steered onto the chunked path by the
TEST (``_row_chunk`` patched to a few 32-row tiles); which path a call takes
in the program is static, by its ``n_assign``, and held here too."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import chainermn_tpu as mn
from chainermn_tpu.parallel import moe as moe_mod

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import moe_rows_parent as parent  # noqa: E402

T, D, F, K, N_HELD, TILE = 96, 16, 8, 4, 4, 32     # a = 384: 32-row tiles


def _distinct(rng, n_experts):
    """``(T, K)`` distinct experts a token, uniform over ``n_experts``."""
    return np.stack([rng.permutation(n_experts)[:K] for _ in range(T)])


def _held25(rng):
    return _distinct(rng, 16)


def _held60(rng):
    return _distinct(rng, 7)                        # 4 of 7 held: 57 %


def _empty_expert(rng):
    idx = _distinct(rng, 16)
    return np.where(idx == 2, 15, idx)              # held expert 2: no token


def _exact_tile(rng):
    idx = 1 + _distinct(rng, 15)                    # nobody chose expert 0 ...
    idx[:2 * TILE, 0] = 0                           # ... but these 64 tokens
    return idx


def _all_held(rng):
    return _distinct(rng, N_HELD)


def _none_held(rng):
    return N_HELD + _distinct(rng, 12)


CASES = {"held25": _held25, "held60": _held60,
         "empty_expert": _empty_expert, "exact_tile": _exact_tile,
         "all_held": _all_held, "none_held": _none_held}


def _inputs(case):
    rng = np.random.default_rng(sorted(CASES).index(case))
    idx = jnp.asarray(CASES[case](rng), jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(39), 6)
    gates = jax.random.uniform(ks[0], (T, K), jnp.float32, 0.05, 1.0)
    x = jax.random.normal(ks[1], (T, D), jnp.bfloat16)
    p = {"w_gate": jax.random.normal(ks[2], (N_HELD, D, F), jnp.bfloat16),
         "w_up": jax.random.normal(ks[3], (N_HELD, D, F), jnp.bfloat16),
         "w_down": jax.random.normal(ks[4], (N_HELD, F, D), jnp.bfloat16)}
    cot = jax.random.normal(ks[5], (T, D), jnp.float32)
    return x, p, idx, gates / gates.sum(-1, keepdims=True), cot


def _layer(x, p, idx, gates, cot):
    """``(y, counts)`` and the gradients by ``x``, the weights and the
    gates of the kernel path (interpreted) under the cotangent ``cot``."""
    def loss(x, p, gates):
        y, counts = moe_mod._held_experts_product(
            x, p, idx, gates, 0, N_HELD, True, True)
        return (y * cot).sum(), (y, counts)

    (_, out), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True))(x, p, gates)
    return out, grads


def _same_bits(got, want, what):
    got, want = np.asarray(got), np.asarray(want)
    assert got.dtype == want.dtype and got.shape == want.shape, what
    # (as numbers: a dead row's cotangent was ``dy[0] * 0``, a signed zero)
    np.testing.assert_array_equal(got.astype(np.float32),
                                  want.astype(np.float32), err_msg=what)


@pytest.fixture
def chunked(monkeypatch, request):
    """The chunked path at tiny sizes: ``request.param`` tiles a chunk (and
    the rows staged, as a training step's are: at these sizes the layer
    would keep them resident, ISSUE 42)."""
    tiles = request.param
    monkeypatch.setattr(moe_mod, "_row_chunk", lambda a, tm: tiles * tm)
    monkeypatch.setattr(moe_mod, "_rows_resident", lambda t, d, a: False)
    return tiles


@pytest.mark.parametrize("chunked", [2, 3], indirect=True,
                         ids=["chunk2", "chunk3"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_layer_is_the_parents_bit_for_bit(case, chunked, monkeypatch):
    """Forward, ``dx`` and every weight gradient bitwise the parent's;
    ``d_gates`` (one float32 dot a row, read back as a scalar) to float32
    reduction order.  3 tiles a chunk: ``M`` (16 tiles) is no multiple."""
    args = _inputs(case)
    (y, counts), (dx, dp, d_gates) = _layer(*args)
    with monkeypatch.context() as m:
        parent.install(m)
        (y0, counts0), (dx0, dp0, d_gates0) = _layer(*args)
    held = int(((args[2] >= 0) & (args[2] < N_HELD)).sum())
    assert int(counts.sum()) == held == int(counts0.sum())
    _same_bits(y, y0, "y")
    _same_bits(dx, dx0, "dx")
    for name in dp0:
        _same_bits(dp[name], dp0[name], name)
    np.testing.assert_allclose(d_gates, d_gates0, rtol=2e-5, atol=1e-5)
    if case == "none_held":
        assert not np.asarray(y).any() and not np.asarray(d_gates).any()
    else:
        assert np.asarray(y).any() and np.asarray(d_gates).any()


@pytest.mark.parametrize("chunked", [3], indirect=True, ids=["chunk3"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_rows_past_the_live_chunks_are_zeros(case, chunked, monkeypatch):
    """The backward's row-side pass alone, on the layer's own work list:
    the live chunks of ``d_rows`` are the parent's bits and every row past
    them is zero — all of them where nothing is held (zero trips)."""
    x, p, idx, gates, cot = _inputs(case)
    seen = {}
    real = moe_mod._combine

    def spy(rows, gates, dest, is_held, row_token, n_live, chunk):
        seen.update(row_token=row_token, dest=dest, is_held=is_held,
                    n_live=n_live, chunk=chunk)
        return real(rows, gates, dest, is_held, row_token, n_live, chunk)

    monkeypatch.setattr(moe_mod, "_combine", spy)
    moe_mod._held_experts_product(x, p, idx, gates, 0, N_HELD, True, True)
    row_token, dest, is_held = (seen[n] for n in
                                ("row_token", "dest", "is_held"))
    n_live, chunk = int(seen["n_live"]), seen["chunk"]
    m = row_token.shape[0]
    assert chunk == 3 * TILE and m % chunk and n_live % TILE == 0
    counts = np.bincount(np.asarray(idx).ravel(), minlength=32)[:N_HELD]
    assert n_live == int((-(-counts // TILE) * TILE).sum())
    if case == "exact_tile":
        assert counts[0] == 2 * TILE
    if case == "empty_expert":
        assert counts[2] == 0
    edge = min(-(-n_live // chunk) * chunk, m)

    rows = jax.random.normal(jax.random.PRNGKey(7), (m, D), jnp.bfloat16)
    _, vjp = jax.vjp(lambda r, g: real(
        r, g, dest, is_held, row_token, seen["n_live"], chunk), rows, gates)
    _, vjp0 = jax.vjp(lambda r, g: parent.combine(
        r, g, dest, is_held, row_token), rows, gates)
    (d_rows, d_gates), (d_rows0, d_gates0) = vjp(cot), vjp0(cot)
    _same_bits(d_rows, d_rows0, "d_rows")
    assert not np.asarray(d_rows[n_live:].astype(jnp.float32)).any()
    np.testing.assert_allclose(d_gates, d_gates0, rtol=2e-5, atol=1e-5)
    assert bool((np.asarray(d_gates) != 0).any()) == bool(n_live)

    # the walk itself, under another pass: a gather of ``x`` by row
    got, = moe_mod._live_chunks(
        lambda token: (jnp.take(x, token, axis=0),), [row_token],
        seen["n_live"], chunk)
    want = jnp.take(x, row_token, axis=0)
    _same_bits(got[:edge], want[:edge], "live chunks")
    assert not np.asarray(got[edge:].astype(jnp.float32)).any()


@jax.custom_vjp
def _nan_where(a, dead):
    """``a`` with NaN in the rows ``dead`` — and its cotangent likewise."""
    return jnp.where(dead, jnp.nan, a)


_nan_where.defvjp(lambda a, dead: (_nan_where(a, dead), dead),
                  lambda dead, ct: (jnp.where(dead, jnp.nan, ct), None))


@pytest.mark.parametrize("chunked", [3], indirect=True, ids=["chunk3"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_nan_in_the_dead_tiles_reaches_nothing(case, chunked, monkeypatch):
    """The fused gate/up product (ISSUE 47) leaves the dead tiles of
    ``hidden``, ``dxs``, ``dg`` and ``du`` unwritten and masks nothing:
    with NaN in every dead tile of its rows, of its result and of both
    their cotangents, the layer's sum and every gradient are finite and the
    clean layer's bits."""
    from chainermn_tpu.ops import moe_gmm as ops

    args = _inputs(case)
    want = _layer(*args)
    real = ops.moe_gmm_glu

    def dirty(xs, w_gate, w_up, tile_expert, n_valid, *, tm, interpret):
        dead = (jnp.arange(xs.shape[0]) // tm >= n_valid)[:, None]
        return _nan_where(real(_nan_where(xs, dead), w_gate, w_up,
                               tile_expert, n_valid, tm=tm,
                               interpret=interpret), dead)

    monkeypatch.setattr(ops, "moe_gmm_glu", dirty)
    got = _layer(*args)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.isfinite(np.asarray(a.astype(jnp.float32))).all()
        _same_bits(a, b, case)


def _n_whiles(jaxpr) -> int:
    """``while`` equations of a jaxpr, the kernels' own bodies left out."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        n += eqn.primitive.name == "while"
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += _n_whiles(sub)
    return n


#: ``(tokens, k)`` of a tick (256 slots), a prefill bucket and the
#: training cell (2 x 8192 tokens): the static ``n_assign`` picks the path
SIZES = {"tick": (256, 2, 0), "prefill_1024": (1024, 8, 0),
         "prefill_8192": (8192, 8, 0), "train_cell": (16384, 8, 1)}


@pytest.mark.parametrize("size", sorted(SIZES))
def test_the_path_is_static_by_the_assignments(size, monkeypatch):
    """A tick's and a prefill's programs hold no ``while`` of the row-side
    pass (they walk the buffer whole, as the parent did: a prefill's forward
    lowers to the parent's text, a tick's to the two kernels that keep its
    rows resident, ISSUE 42); the training cell's forward holds none either
    (its rows are gathered whole from fast memory), its backward one."""
    t, k, loops = SIZES[size]
    tm = moe_mod._row_tile(t * k)
    chunk = moe_mod._row_chunk(t * k, tm)
    assert (chunk is not None) == bool(loops)
    if chunk:
        assert chunk % tm == 0 and chunk == 8192
    shapes = (jax.ShapeDtypeStruct((t, D), jnp.bfloat16),
              {"w_gate": jax.ShapeDtypeStruct((N_HELD, D, F), jnp.bfloat16),
               "w_up": jax.ShapeDtypeStruct((N_HELD, D, F), jnp.bfloat16),
               "w_down": jax.ShapeDtypeStruct((N_HELD, F, D), jnp.bfloat16)},
              jax.ShapeDtypeStruct((t, k), jnp.int32),
              jax.ShapeDtypeStruct((t, k), jnp.float32))

    def programs():
        # (fresh functions a reading: tracing is cached by the function)
        def forward(x, p, idx, gates):
            return moe_mod._held_experts_product(
                x, p, idx, gates, 0, N_HELD, True, True)[0]

        def both(x, p, idx, gates):
            return jax.grad(lambda x, p, g: jax.checkpoint(forward)(
                x, p, idx, g).sum(), argnums=(0, 1, 2))(x, p, gates)

        return (jax.make_jaxpr(forward)(*shapes),
                jax.make_jaxpr(both)(*shapes),
                None if loops else jax.jit(forward).lower(*shapes).as_text())

    fwd, both, text = programs()
    with monkeypatch.context() as m:
        parent.install(m)
        fwd0, both0, text0 = programs()
    assert _n_whiles(fwd.jaxpr) == _n_whiles(fwd0.jaxpr)
    assert _n_whiles(both.jaxpr) - _n_whiles(both0.jaxpr) == loops
    assert text == text0            # the served forward: the parent's text
    if not loops:                   # ... but for a tick's resident rows
        resident = moe_mod._rows_resident(t, D, t * k)
        assert resident == (size == "tick")
        assert ("moe_gmm_rows" in text) == ("moe_gmm_sum" in text) == resident


def test_the_chunks_carry_the_rows_varying_type(devices):
    """Inside ``shard_map`` with vma checking on (the train step's), the
    zero buffer the loop carries takes the gathered rows' varying axes."""
    mesh = mn.make_nd_mesh(("data", "model"), (1, 1), devices[:1])
    x = jax.random.normal(jax.random.PRNGKey(0), (T, D), jnp.bfloat16)
    row_token = jax.random.randint(jax.random.PRNGKey(1), (8 * TILE,), 0, T)

    def rows(x, row_token):
        return moe_mod._live_chunks(
            lambda token: (jnp.take(x, token, axis=0),), [row_token],
            row_token[0] * 0 + 5 * TILE, 2 * TILE)[0]

    got = jax.jit(jax.shard_map(
        rows, mesh=mesh, in_specs=(P("data"), P()), out_specs=P("data"),
        check_vma=True))(x, row_token)
    want = jnp.take(x, row_token, axis=0).at[6 * TILE:].set(0)
    _same_bits(got, want, "rows")
