"""Portable redistribution primitive (parallel/reshard.py) — ISSUE 8.

Three contracts under test:

* **Value exactness** — ``make_reshard`` over random pytrees × every
  (src, dst) spec pair is the identity on VALUES: only placement moves.
* **Cost honesty** — the wire legs route through the ACCOUNTED
  collective face, so the comm ledger's booked bytes equal
  ``reshard_cost``'s static prediction (the same number the shard-flow
  model derives; the registered ``parallel.reshard`` entry point holds
  the jaxpr side byte-exact in ``pytest -m lint``).
* **Host twin** — ``reshard_host`` re-partitions pickled checkpoint
  shards between world sizes with the same spec language, no devices.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu import observability as obs
from chainermn_tpu import topology
from chainermn_tpu.parallel.reshard import (
    make_reshard,
    reshard_cost,
    reshard_host,
    reshard_tree_cost,
    validate_spec,
)

AX = "mn"
MESH_N = 4


@pytest.fixture
def mesh(devices):
    return topology.make_nd_mesh((AX,), (MESH_N,), devices[:MESH_N])


@pytest.fixture
def tracing():
    obs.reset_all()
    obs.enable()
    yield obs.get_tracer()
    obs.disable()
    obs.reset_all()


def _rand_tree(seed: int):
    """Random pytree whose leaf axes all divide the mesh size."""
    rng = np.random.RandomState(seed)
    def arr(*shape):
        return rng.randn(*shape).astype(np.float32)
    return {
        "a": arr(8, 12),
        "nested": {"b": arr(4, 8, 16), "c": arr(16,)},
        "lst": [arr(8, 4), arr(12, 8)],
    }


#: every meaningful 2-D-capable (src, dst) leaf-spec pair
SPEC_PAIRS = [
    (None, None),   # no-op
    (None, 0),      # replicated -> sharded: local slice, 0 wire bytes
    (0, None),      # sharded -> replicated: all_gather
    (0, 0),         # no-op (already there)
    (0, 1),         # resharding: ONE all_to_all
    (1, 0),
]


class TestReshardDevice:
    @pytest.mark.parametrize("src,dst", SPEC_PAIRS)
    def test_value_exactness_random_trees(self, mesh, src, dst):
        """Redistribution is the identity on values for every pair."""
        tree = _rand_tree(seed=hash((str(src), str(dst))) % 2**31)
        # 1-D leaves can't shard on axis 1 — drop them for those pairs
        if 1 in (src, dst):
            tree = {"a": tree["a"], "nested": {"b": tree["nested"]["b"]},
                    "lst": tree["lst"]}
        fn = make_reshard(mesh, src, dst)
        out = fn(tree)
        jax.tree_util.tree_map(
            lambda o, x: np.testing.assert_array_equal(np.asarray(o), x),
            out, tree)

    def test_spec_pytree_per_leaf(self, mesh):
        """A spec pytree reshards each leaf differently in one program."""
        tree = {"w": np.arange(32, dtype=np.float32).reshape(8, 4),
                "m": np.arange(16, dtype=np.float32)}
        src = {"w": 0, "m": None}
        dst = {"w": 1, "m": 0}
        out = make_reshard(mesh, src, dst)(tree)
        np.testing.assert_array_equal(np.asarray(out["w"]), tree["w"])
        np.testing.assert_array_equal(np.asarray(out["m"]), tree["m"])

    def test_output_carries_dst_sharding(self, mesh):
        x = {"v": np.arange(64, dtype=np.float32).reshape(8, 8)}
        out = make_reshard(mesh, 0, None)(x)["v"]
        # replicated output: every device holds the full array
        assert all(s.data.shape == (8, 8)
                   for s in out.addressable_shards)
        out2 = make_reshard(mesh, None, 0)(x)["v"]
        assert all(s.data.shape == (2, 8)
                   for s in out2.addressable_shards)

    @pytest.mark.parametrize("src,dst,primitive", [
        ((0), None, "all_gather"),
        (0, 1, "all_to_all"),
    ])
    def test_ledger_bytes_match_static_prediction(self, tracing, mesh,
                                                  src, dst, primitive):
        """Acceptance: for ≥2 (src, dst) pairs the comm ledger's runtime
        bytes equal the static prediction (``reshard_cost`` — the same
        formula the shard-flow model reconciles in ``pytest -m lint``)."""
        tree = {"x": np.zeros((8, 16), np.float32),
                "y": np.zeros((16, 8), np.float32)}
        want = reshard_tree_cost(tree, src, dst, MESH_N)
        row0 = obs.comm_report()["per_op"].get(
            f"{primitive}@{AX}", {"calls": 0, "bytes": 0})
        make_reshard(mesh, src, dst)(tree)
        row = obs.comm_report()["per_op"][f"{primitive}@{AX}"]
        assert row["bytes"] - row0["bytes"] == want["ledger_bytes"]
        assert row["calls"] - row0["calls"] == \
            want["per_primitive"][primitive]["calls"]

    def test_zero_wire_pairs_book_nothing(self, tracing, mesh):
        """R→S and no-op pairs move zero bytes — and the static model
        says so too."""
        tree = {"x": np.zeros((8, 8), np.float32)}
        for src, dst in [(None, 0), (None, None), (0, 0)]:
            before = {k: dict(v) for k, v in
                      obs.comm_report()["per_op"].items()}
            make_reshard(mesh, src, dst)(tree)
            after = obs.comm_report()["per_op"]
            for op in ("all_gather", "all_to_all"):
                key = f"{op}@{AX}"
                assert after.get(key, {}).get("bytes", 0) == \
                    before.get(key, {}).get("bytes", 0), (src, dst)
            assert reshard_tree_cost(tree, src, dst,
                                     MESH_N)["wire_bytes"] == 0

    def test_indivisible_axis_raises(self, mesh):
        with pytest.raises(ValueError, match="% 4"):
            make_reshard(mesh, None, 0)({"x": np.zeros((6, 8),
                                                       np.float32)})

    def test_one_compiled_program_per_spec_pair(self, mesh):
        """Repeated transfers hit the jit cache (slot indices and specs
        are static by construction) — the KV-slab-transfer contract."""
        tree = {"x": np.arange(32, dtype=np.float32).reshape(8, 4)}
        fn = make_reshard(mesh, 0, None)
        fn(tree)
        fn({"x": np.ones((8, 4), np.float32)})   # same shape: cache hit
        assert len(fn.programs) == 1
        (jitted,) = fn.programs.values()
        assert jitted._cache_size() == 1
        fn({"x": np.ones((16, 4), np.float32)})  # new shape: new program
        assert len(fn.programs) == 2


class TestReshardCostModel:
    def test_all_gather_wire_bytes(self):
        c = reshard_cost((8, 16), np.float32, 0, None, 4)
        block = 8 * 16 * 4 // 4
        assert c["primitive"] == "all_gather"
        assert c["ledger_bytes"] == block
        assert c["wire_bytes"] == block * (4 - 1)

    def test_all_to_all_wire_bytes(self):
        c = reshard_cost((8, 16), np.float32, 0, 1, 4)
        block = 8 * 16 * 4 // 4
        assert c["primitive"] == "all_to_all"
        # each rank keeps 1/P of its block: (P-1)/P crosses the wire
        assert c["wire_bytes"] == block * (4 - 1) // 4

    def test_axis_size_one_is_free(self):
        assert reshard_cost((8,), np.float32, 0, None, 1)["wire_bytes"] == 0

    def test_validate_spec(self):
        assert validate_spec(None) is None
        assert validate_spec(-1, ndim=2) == 1
        with pytest.raises(TypeError):
            validate_spec("0")
        with pytest.raises(TypeError):
            validate_spec(True)
        with pytest.raises(ValueError):
            validate_spec(3, ndim=2)


class TestReshardHost:
    """The device-free twin: checkpoint-shard re-partitioning."""

    def _shards(self, n, sharded_len=24):
        """n per-process pytrees: replicated params, axis-0-sharded
        moment vector, per-rank counter."""
        full = np.arange(sharded_len, dtype=np.float32)
        block = sharded_len // n
        return full, [
            {"w": np.full((3, 3), 7.0), "m": full[r * block:(r + 1) * block],
             "rank_tag": r}
            for r in range(n)
        ]

    @pytest.mark.parametrize("src_n,dst_n", [(4, 2), (2, 4), (4, 3), (2, 1)])
    def test_world_size_change_exact(self, src_n, dst_n):
        full, shards = self._shards(src_n)
        spec = {"w": None, "m": 0, "rank_tag": "per_rank"}
        out = reshard_host(shards, spec, spec, dst_n)
        assert len(out) == dst_n
        # replicated: bit-for-bit shard-0 value everywhere
        for s in out:
            np.testing.assert_array_equal(s["w"], shards[0]["w"])
        # sharded: concat of destination blocks == the logical array
        np.testing.assert_array_equal(
            np.concatenate([s["m"] for s in out]), full)
        # per_rank: new rank r inherits old rank r % src_n
        assert [s["rank_tag"] for s in out] == \
            [r % src_n for r in range(dst_n)]

    def test_random_pytrees_round_trip(self):
        """n=4 → n=2 → n=4 is the identity on every leaf."""
        rng = np.random.RandomState(0)
        full = {"a": rng.randn(8, 6).astype(np.float32),
                "b": {"c": rng.randn(16,).astype(np.float32)}}
        spec = {"a": 0, "b": {"c": 0}}
        shards4 = reshard_host([full], None, spec, 4)
        # sanity: 4 blocks of 2 rows each
        assert shards4[0]["a"].shape == (2, 6)
        shards2 = reshard_host(shards4, spec, spec, 2)
        back4 = reshard_host(shards2, spec, spec, 4)
        for a, b in zip(shards4, back4):
            jax.tree_util.tree_map(np.testing.assert_array_equal, a, b)

    def test_uneven_split_raises(self):
        _, shards = self._shards(2, sharded_len=8)
        with pytest.raises(ValueError, match="does not divide"):
            reshard_host(shards, {"w": None, "m": 0, "rank_tag": "per_rank"},
                         {"w": None, "m": 0, "rank_tag": "per_rank"}, 3)

    def test_structure_mismatch_raises(self):
        shards = [{"a": np.zeros(2)}, {"a": np.zeros(2), "b": 1}]
        with pytest.raises(ValueError, match="disagree on structure"):
            reshard_host(shards, None, None, 2)

    def test_per_rank_cannot_reshard_to_array(self):
        _, shards = self._shards(2)
        with pytest.raises(ValueError, match="per_rank"):
            reshard_host(shards, {"w": None, "m": 0, "rank_tag": "per_rank"},
                         {"w": None, "m": 0, "rank_tag": 0}, 2)

    def test_empty_and_bad_counts(self):
        with pytest.raises(ValueError, match="empty"):
            reshard_host([], None, None, 2)
        with pytest.raises(ValueError, match=">= 1"):
            reshard_host([{"a": np.zeros(2)}], None, None, 0)
