"""Parity tests for the Pallas in-place cache append (interpret mode).

Oracle: ``dynamic_update_slice_in_dim`` — cache_append's XLA fallback IS
that op, so the Pallas path must match it bit-for-bit.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.ops.kv_cache import cache_append


def _mk(shape, dtype, seed):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape), dtype)


@pytest.mark.parametrize("pos", [0, 5, 7, 8, 123, 127])
def test_second_minor_axis_4d(pos):
    # greedy layout before flattening: (B, H, S, D), position axis 2
    b, h, s, d = 2, 4, 128, 16
    kc, vc = _mk((b, h, s, d), jnp.float32, 0), _mk((b, h, s, d),
                                                    jnp.float32, 1)
    kn, vn = _mk((b, h, 1, d), jnp.float32, 2), _mk((b, h, 1, d),
                                                    jnp.float32, 3)
    got_k, got_v = cache_append(kc, vc, kn, vn, pos, axis=2,
                                impl="pallas", interpret=True)
    want_k = jax.lax.dynamic_update_slice_in_dim(kc, kn, pos, 2)
    want_v = jax.lax.dynamic_update_slice_in_dim(vc, vn, pos, 2)
    np.testing.assert_array_equal(np.asarray(got_k), np.asarray(want_k))
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))


def test_flat_3d_layout_and_dtype():
    # the flat greedy cache: (B, S, H*D), position axis 1 (second-minor)
    b, s, d = 3, 64, 32
    kc, vc = _mk((b, s, d), jnp.bfloat16, 4), _mk((b, s, d), jnp.bfloat16, 5)
    kn, vn = _mk((b, 1, d), jnp.bfloat16, 6), _mk((b, 1, d), jnp.bfloat16, 7)
    got_k, got_v = cache_append(kc, vc, kn, vn, 33, axis=1,
                                impl="pallas", interpret=True)
    want_k = jax.lax.dynamic_update_slice_in_dim(kc, kn, 33, 1)
    np.testing.assert_array_equal(np.asarray(got_k), np.asarray(want_k))
    assert got_k.dtype == jnp.bfloat16


def test_beam_5d_layout():
    # lazy-beam generated caches: (B, slot, H, max_new, D), axis 3
    b, k, h, t, d = 2, 3, 2, 16, 8
    kc, vc = _mk((b, k, h, t, d), jnp.float32, 8), _mk((b, k, h, t, d),
                                                       jnp.float32, 9)
    kn, vn = (_mk((b, k, h, 1, d), jnp.float32, 10),
              _mk((b, k, h, 1, d), jnp.float32, 11))
    got_k, _ = cache_append(kc, vc, kn, vn, 9, axis=3,
                            impl="pallas", interpret=True)
    want_k = jax.lax.dynamic_update_slice_in_dim(kc, kn, 9, 3)
    np.testing.assert_array_equal(np.asarray(got_k), np.asarray(want_k))


def test_traced_position():
    b, s, d = 2, 32, 16
    kc = _mk((b, s, d), jnp.float32, 12)
    kn = _mk((b, 1, d), jnp.float32, 13)

    @jax.jit
    def go(pos):
        return cache_append(kc, kc, kn, kn, pos, axis=1, impl="pallas",
                            interpret=True)[0]

    for pos in (0, 15, 31):
        np.testing.assert_array_equal(
            np.asarray(go(pos)),
            np.asarray(jax.lax.dynamic_update_slice_in_dim(kc, kn, pos, 1)))


def test_envelope_rejections_and_fallback():
    kc = jnp.zeros((2, 30, 16))  # extent 30 not 8-divisible
    kn = jnp.zeros((2, 1, 16))
    with pytest.raises(ValueError, match="second-minor"):
        cache_append(kc, kc, kn, kn, 3, axis=1, impl="pallas")
    # auto on a non-TPU backend (or unfittable shape) = the dus fallback
    got, _ = cache_append(kc, kc, kn + 1, kn + 1, 3, axis=1, impl="auto")
    np.testing.assert_array_equal(
        np.asarray(got),
        np.asarray(jax.lax.dynamic_update_slice_in_dim(kc, kn + 1, 3, 1)))
    with pytest.raises(ValueError, match="impl"):
        cache_append(kc, kc, kn, kn, 3, impl="bogus")


@pytest.mark.parametrize("rows,pos", [(2, 0), (2, 6), (2, 30), (4, 8),
                                      (4, 28), (8, 16)])
def test_multi_row_range_scatter(rows, pos):
    """rows|8 writes at rows-aligned positions (the time-major beam tick
    writes all k slots' rows [(i-1)k, ik) in one call)."""
    b, s, d = 2, 32, 16
    kc, vc = _mk((b, s, d), jnp.float32, 20), _mk((b, s, d), jnp.float32, 21)
    kn, vn = (_mk((b, rows, d), jnp.float32, 22),
              _mk((b, rows, d), jnp.float32, 23))
    got_k, got_v = cache_append(kc, vc, kn, vn, pos, axis=1,
                                impl="pallas", interpret=True)
    want_k = jax.lax.dynamic_update_slice_in_dim(kc, kn, pos, 1)
    want_v = jax.lax.dynamic_update_slice_in_dim(vc, vn, pos, 1)
    np.testing.assert_array_equal(np.asarray(got_k), np.asarray(want_k))
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))


def test_rows_not_dividing_8_falls_back():
    kc = jnp.zeros((2, 32, 16))
    kn = jnp.ones((2, 3, 16))
    got, _ = cache_append(kc, kc, kn, kn, 6, axis=1, impl="auto")
    np.testing.assert_array_equal(
        np.asarray(got),
        np.asarray(jax.lax.dynamic_update_slice_in_dim(kc, kn, 6, 1)))
    with pytest.raises(ValueError, match="rows dividing"):
        cache_append(kc, kc, kn, kn, 6, axis=1, impl="pallas")


class TestPerRowPositions:
    """Per-row position vectors (the serving pool's ragged tick): row b
    writes at pos[b].  Oracle: stacked per-row dynamic_update_slice."""

    def _oracle(self, kc, kn, pos, axis):
        rows = [jax.lax.dynamic_update_slice_in_dim(
            kc[b], kn[b], int(pos[b]), axis - 1)
            for b in range(kc.shape[0])]
        return np.stack([np.asarray(r) for r in rows])

    def test_vector_pos_matches_per_row_dus(self):
        b, s, d = 4, 32, 16
        kc, vc = _mk((b, s, d), jnp.float32, 30), _mk((b, s, d),
                                                      jnp.float32, 31)
        kn, vn = _mk((b, 1, d), jnp.float32, 32), _mk((b, 1, d),
                                                      jnp.float32, 33)
        pos = jnp.asarray([0, 5, 31, 17], jnp.int32)  # ragged, unaligned
        got_k, got_v = cache_append(kc, vc, kn, vn, pos, axis=1)
        np.testing.assert_array_equal(np.asarray(got_k),
                                      self._oracle(kc, kn, pos, 1))
        np.testing.assert_array_equal(np.asarray(got_v),
                                      self._oracle(vc, vn, pos, 1))

    def test_vector_pos_under_jit_with_traced_positions(self):
        b, s, d = 3, 16, 8
        kc = _mk((b, s, d), jnp.bfloat16, 34)
        kn = _mk((b, 1, d), jnp.bfloat16, 35)

        @jax.jit
        def go(pos):
            return cache_append(kc, kc, kn, kn, pos, axis=1)[0]

        pos = jnp.asarray([2, 9, 15], jnp.int32)
        np.testing.assert_array_equal(np.asarray(go(pos)),
                                      self._oracle(kc, kn, pos, 1))
        assert go(pos).dtype == jnp.bfloat16

    def test_all_equal_vector_matches_scalar(self):
        b, s, d = 2, 32, 16
        kc = _mk((b, s, d), jnp.float32, 36)
        kn = _mk((b, 1, d), jnp.float32, 37)
        vec, _ = cache_append(kc, kc, kn, kn,
                              jnp.full((b,), 11, jnp.int32), axis=1)
        sca, _ = cache_append(kc, kc, kn, kn, 11, axis=1)
        np.testing.assert_array_equal(np.asarray(vec), np.asarray(sca))

    def test_multi_row_writes_per_row(self):
        # each row writes a 2-row slab at its own position
        b, s, r, d = 2, 24, 2, 8
        kc = _mk((b, s, d), jnp.float32, 38)
        kn = _mk((b, r, d), jnp.float32, 39)
        pos = jnp.asarray([3, 20], jnp.int32)
        got, _ = cache_append(kc, kc, kn, kn, pos, axis=1)
        np.testing.assert_array_equal(np.asarray(got),
                                      self._oracle(kc, kn, pos, 1))

    def test_vector_pos_rejections(self):
        kc = jnp.zeros((2, 32, 16))
        kn = jnp.ones((2, 1, 16))
        with pytest.raises(ValueError, match="scalar pos only"):
            cache_append(kc, kc, kn, kn, jnp.asarray([1, 2]), axis=1,
                         impl="pallas")
        with pytest.raises(ValueError, match="length"):
            cache_append(kc, kc, kn, kn, jnp.asarray([1, 2, 3]), axis=1)
        with pytest.raises(ValueError, match="row axis"):
            cache_append(kc.T, kc.T, kn, kn, jnp.asarray([1, 2]), axis=0)


def test_pallas_on_non_tpu_backend_raises_descriptive_error():
    # A VALID envelope forced onto compiled Pallas off-chip must fail at
    # dispatch with an actionable message, not deep in Mosaic lowering.
    kc = jnp.zeros((2, 32, 16))
    kn = jnp.ones((2, 1, 16))
    with pytest.raises(ValueError, match="requires a TPU backend"):
        cache_append(kc, kc, kn, kn, 6, axis=1, impl="pallas",
                     interpret=False)
    # interpret mode stays available off-chip
    got, _ = cache_append(kc, kc, kn, kn, 6, axis=1, impl="pallas",
                          interpret=True)
    np.testing.assert_array_equal(
        np.asarray(got),
        np.asarray(jax.lax.dynamic_update_slice_in_dim(kc, kn, 6, 1)))


# --------------------------------------------------------------------------
# the served tick's writer: per-slot positions, the busy slots alone
# --------------------------------------------------------------------------

def _oracle_rows(c, new, pos, busy):
    """Stacked per-row ``dynamic_update_slice`` (which clamps the start
    inside the buffer); a slot that is not busy keeps its buffer."""
    return np.stack([
        np.asarray(jax.lax.dynamic_update_slice_in_dim(
            c[b], new[b], int(pos[b]), 0) if busy is None or busy[b]
            else c[b], np.float32)
        for b in range(c.shape[0])])


_N = 6
_BUSY = {"all_by_none": None, "none": [], "one": [4], "some": [0, 2, 5],
         "all": list(range(_N))}
#: name -> (rows a buffer, columns of each buffer of the layer, window)
_LAYERS = {"kv_rows": (48, (1024, 1024), 0),
           "latent": (48, (640,), 0),
           "ring": (32, (256, 256), 32)}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("layer", list(_LAYERS))
@pytest.mark.parametrize("busy", list(_BUSY))
def test_write_rows_busy_slots(busy, layer, dtype):
    """``write_rows``' kernel (interpret mode) against the per-row oracle:
    positions at 0, the block edges of both dtypes (7, 8, 15, 16), ``rows
    - 1`` and beyond it (clamped), or a ring's ``pos % window``; a slot
    that is not busy comes back bit for bit; ``busy`` None is the vector
    path as it was; the XLA path beside the kernel gives the same."""
    from chainermn_tpu.ops.kv_cache import busy_slots, write_rows

    total, columns, window = _LAYERS[layer]
    mask = None if _BUSY[busy] is None else np.isin(np.arange(_N),
                                                    _BUSY[busy])
    pos = np.asarray([0, 7, 8, 15, 16, total - 1], np.int32)
    if window:
        pos = (pos + np.asarray([0, 3, 5, 1, 2, 4]) * window) % window
    for shift in (0, 1):        # second pass: 1, 8, 9, 16, 17, past the end
        at = jnp.asarray(pos + shift if not window
                         else (pos + shift) % window)
        bufs = tuple(_mk((_N, total, c), dtype, 40 + i)
                     for i, c in enumerate(columns))
        new = tuple(_mk((_N, 1, c), dtype, 50 + i)
                    for i, c in enumerate(columns))
        bmask = None if mask is None else jnp.asarray(mask)
        got = write_rows(bufs, new, at, bmask, interpret=True)
        handed = write_rows(bufs, new, at, bmask, interpret=True,
                            slots=busy_slots(bmask, _N))
        plain = write_rows(bufs, new, at, bmask)            # the XLA path
        assert len(got) == len(bufs)
        for c, n, g, h, x in zip(bufs, new, got, handed, plain):
            want = _oracle_rows(c, n, np.asarray(at), mask)
            assert g.dtype == c.dtype
            np.testing.assert_array_equal(np.asarray(g, np.float32), want)
            np.testing.assert_array_equal(np.asarray(h, np.float32), want)
            np.testing.assert_array_equal(np.asarray(x, np.float32), want)
            if mask is None:    # today's vector path, bit for bit
                v, _ = cache_append(c, c, n, n, at, axis=1, impl="xla")
                np.testing.assert_array_equal(np.asarray(v, np.float32),
                                              want)


def test_write_rows_under_jit_and_rejections():
    from chainermn_tpu.ops.kv_cache import busy_slots, write_rows

    c = _mk((4, 32, 128), jnp.bfloat16, 60)
    n = _mk((4, 1, 128), jnp.bfloat16, 61)
    busy = jnp.asarray([True, False, False, True])

    @jax.jit
    def go(c, pos, busy):
        return write_rows((c,), (n,), pos, busy, interpret=True)[0]

    for pos in ([0, 1, 2, 3], [31, 30, 16, 15]):
        np.testing.assert_array_equal(
            np.asarray(go(c, jnp.asarray(pos, jnp.int32), busy), np.float32),
            _oracle_rows(c, n, pos, np.asarray(busy)))
    slots = busy_slots(busy, 4)
    assert int(slots.n[0]) == 2 and list(np.asarray(slots.slot)) == [0, 3,
                                                                     3, 3]
    assert int(busy_slots(None, 4).n[0]) == 4
    # 30 rows are no whole sublane blocks; two rows a slot are no tick
    odd = jnp.zeros((4, 30, 128), jnp.bfloat16)
    with pytest.raises(ValueError, match="whole sublane blocks"):
        write_rows((odd,), (n,), jnp.zeros(4, jnp.int32), interpret=True)
    with pytest.raises(ValueError, match="whole sublane blocks"):
        write_rows((c,), (jnp.zeros((4, 2, 128), jnp.bfloat16),),
                   jnp.zeros(4, jnp.int32), interpret=True)
    with pytest.raises(ValueError, match="length"):
        write_rows((c,), (n,), jnp.zeros(3, jnp.int32))
    # off the kernel's envelope the XLA path keeps the busy contract
    got = write_rows((odd + 1,), (n,), jnp.asarray([0, 5, 29, 40]), busy)[0]
    np.testing.assert_array_equal(
        np.asarray(got, np.float32),
        _oracle_rows(odd + 1, n, [0, 5, 29, 40], np.asarray(busy)))
