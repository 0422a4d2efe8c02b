"""Extension tests.

Reference parity: ``tests/extensions_tests/test_checkpoint.py`` (save /
maybe_load round-trip, generation GC) and ``test_allreduce_persistent.py``
(BN stats averaged) [uv] — SURVEY.md §4 — plus observation aggregation and
the except hook's single-process passthrough.
"""

import os
import sys

import numpy as np
import pytest

import chainermn_tpu as mn
from chainermn_tpu import global_except_hook
from chainermn_tpu.extensions import (
    aggregate_observations,
    allreduce_persistent,
    create_multi_node_checkpointer,
)
from chainermn_tpu.iterators import SerialIterator


@pytest.fixture(scope="module")
def comm(devices):
    return mn.create_communicator("xla", devices=devices)


@pytest.fixture()
def naive():
    return mn.create_communicator("naive", size=4)


class TestCheckpointer:
    def _state(self, step):
        return {
            "params": {"w": np.full((3, 3), float(step)), "b": np.arange(3.0)},
            "step": step,
        }

    def test_save_maybe_load_roundtrip(self, comm, tmp_path):
        cp = create_multi_node_checkpointer("job", comm, path=str(tmp_path))
        assert cp.maybe_load()[1] is None  # fresh start: no-op
        cp.save(self._state(7), iteration=7)
        cp.save(self._state(9), iteration=9)
        loaded, it = cp.maybe_load()
        assert it == 9
        np.testing.assert_array_equal(loaded["params"]["w"], np.full((3, 3), 9.0))
        assert loaded["step"] == 9

    def test_resume_keeps_passed_state_when_empty(self, comm, tmp_path):
        cp = create_multi_node_checkpointer("job", comm, path=str(tmp_path))
        template = {"x": 1}
        state, it = cp.maybe_load(template)
        assert it is None and state is template

    def test_generation_gc(self, comm, tmp_path):
        cp = create_multi_node_checkpointer(
            "job", comm, gc_interval=3, keep=2, path=str(tmp_path))
        for i in range(1, 8):
            cp.save(self._state(i), iteration=i)
        # GC ran after saves 3 (keeps 2,3) and 6 (keeps 5,6); save 7 arrived
        # after the last GC.
        assert cp.get_generations() == [5, 6, 7]

    def test_world_size_mismatch_fails_loudly(self, comm, tmp_path):
        cp = create_multi_node_checkpointer("job", comm, path=str(tmp_path))
        cp.save(self._state(1), iteration=1)
        cp.flush()  # async writer: the shard must be on disk before renaming
        # Simulate a restart with a different world size by renaming the
        # shard's world-size tag.
        import os
        (old,) = [f for f in os.listdir(tmp_path)
                  if not f.startswith(".") and "manifest" not in f]
        os.rename(tmp_path / old, tmp_path / old.replace("of1", "of4"))
        # the stray world-4 shard has no world-4 manifest, so it is not
        # elastically restorable either — still a loud collective error
        with pytest.raises(RuntimeError, match="world size"):
            cp.maybe_load()

    def test_iterator_state_checkpointable(self, comm, tmp_path):
        ds = [(np.float32(i), i % 2) for i in range(20)]
        it = SerialIterator(ds, 3, shuffle=True, seed=0)
        for _ in range(3):
            it.next()
        cp = create_multi_node_checkpointer("it", comm, path=str(tmp_path))
        cp.save({"iterator": it.state_dict()}, iteration=3)
        expect = [x[0] for x in it.next()]
        loaded, _ = cp.maybe_load()
        it2 = SerialIterator(ds, 3, shuffle=True, seed=99)
        it2.load_state_dict(loaded["iterator"])
        assert [x[0] for x in it2.next()] == expect

    def test_device_arrays_detached(self, comm, tmp_path):
        import jax.numpy as jnp
        cp = create_multi_node_checkpointer("dev", comm, path=str(tmp_path))
        cp.save({"p": jnp.ones((4,))}, iteration=1)
        loaded, _ = cp.maybe_load()
        assert isinstance(loaded["p"], np.ndarray)

    def test_finalize_cleans_up(self, comm, tmp_path):
        cp = create_multi_node_checkpointer("job", comm, path=str(tmp_path))
        cp.save(self._state(1), iteration=1)
        cp.finalize()
        assert cp.maybe_load()[1] is None


class TestAsyncCheckpointWrites:
    """Orbax-style async writer (SURVEY §5 build note): saves return before
    disk IO, reads join the writer, writer errors surface at the next call."""

    def test_async_is_default_and_joins_on_read(self, comm, tmp_path):
        cp = create_multi_node_checkpointer("job", comm, path=str(tmp_path))
        assert cp._async
        state = {"w": np.arange(6.0)}
        cp.save(state, iteration=3)
        loaded, it = cp.maybe_load()  # joins the writer first
        assert it == 3
        np.testing.assert_array_equal(loaded["w"], state["w"])

    def test_unpicklable_state_fails_at_save(self, comm, tmp_path):
        """Serialization happens on the CALLER thread (a writer-thread
        pickle would capture live references the train loop mutates), so a
        bad state fails loudly at save() itself."""
        cp = create_multi_node_checkpointer("job", comm, path=str(tmp_path))
        with pytest.raises(Exception, match="pickle|local object"):
            cp.save({"bad": lambda: None}, iteration=1)
        assert cp.get_generations() == []

    def test_finalize_cleans_up_even_after_writer_error(self, comm, tmp_path):
        cp = create_multi_node_checkpointer("job", comm, path=str(tmp_path))
        cp.save({"x": 1}, iteration=1)
        cp.flush()
        # park an artificial writer failure
        cp._submit(lambda: (_ for _ in ()).throw(OSError("disk gone")))
        with pytest.raises(OSError, match="disk gone"):
            cp.finalize()
        # the cleanup contract ran anyway: no shards left behind
        assert cp._local_files(any_world_size=True) == []

    def test_sync_mode_still_available(self, comm, tmp_path):
        cp = create_multi_node_checkpointer(
            "job", comm, path=str(tmp_path), async_write=False)
        cp.save({"x": 1}, iteration=2)
        assert cp.maybe_load()[1] == 2

    def test_steps_to_recover_with_and_without_final_save(self, comm,
                                                          tmp_path):
        """Periodic saves at 5 and 10, preempted at 13: with the
        preemption handler's final save resume replays 0 steps; with that
        shard gone (SIGKILL, no final save) it replays 13 - 10 = 3."""
        cp = create_multi_node_checkpointer(
            "job", comm, path=str(tmp_path), keep=10, async_write=False)
        for it in (5, 10, 13):
            cp.save({"iteration": it}, iteration=it)
        assert 13 - cp.maybe_load()[1] == 0
        os.unlink(cp._filename(13))
        assert 13 - cp.maybe_load()[1] == 3

    def test_save_does_not_block_on_disk_io(self, comm, tmp_path):
        """The save call itself should return in ~detach time: its write is
        still in flight (or done) but never serialized inline.  We assert
        behavior, not timing: the file may lag the call, yet maybe_load
        (which joins) always sees it."""
        cp = create_multi_node_checkpointer("job", comm, path=str(tmp_path))
        big = {"w": np.zeros((256, 256), np.float32)}
        for i in range(5):
            cp.save(big, iteration=i)
        assert cp.maybe_load()[1] == 4


class TestAllreducePersistent:
    def test_bn_stats_averaged(self, naive):
        # 4 ranks with divergent running stats → synced to the mean.
        stacked = {
            "mean": np.stack([np.full(5, r, np.float32) for r in range(4)]),
            "var": np.stack([np.full(5, 2.0 * r, np.float32) for r in range(4)]),
        }
        out = allreduce_persistent(stacked, naive)
        np.testing.assert_allclose(out["mean"], np.full((4, 5), 1.5))
        np.testing.assert_allclose(out["var"], np.full((4, 5), 3.0))

    def test_xla_matches_naive(self, comm):
        stacked = np.stack([np.full((2, 3), r, np.float32) for r in range(8)])
        out = np.asarray(allreduce_persistent({"m": stacked}, comm)["m"])
        np.testing.assert_allclose(out, np.full((8, 2, 3), 3.5))


class TestObservationAggregator:
    def test_scalar_mean_identity_single_controller(self, comm):
        obs = {"loss": 2.5, "accuracy": 0.75}
        out = aggregate_observations(obs, comm)
        assert out["loss"] == pytest.approx(2.5)
        assert out["accuracy"] == pytest.approx(0.75)


class TestWatchdog:
    """Hang detection (SURVEY §5: the reference only mitigated deadlocks
    via the except hook; a silent hang waited forever)."""

    def test_fires_on_stall_and_not_on_heartbeat(self):
        from chainermn_tpu.extensions import Watchdog

        fired = []
        wd = Watchdog(timeout=0.3, poll_interval=0.05,
                      action=lambda gap, to: fired.append((gap, to)))
        wd.initialize(trainer=None)
        # heartbeats keep it quiet
        import time
        for _ in range(4):
            time.sleep(0.1)
            wd.observe(trainer=None)
        assert not fired
        # stall → fires once
        time.sleep(0.6)
        assert fired and fired[0][0] > 0.3
        wd.finalize()

    def test_finalize_stops_thread_before_timeout(self):
        from chainermn_tpu.extensions import Watchdog

        fired = []
        wd = Watchdog(timeout=0.5, poll_interval=0.05,
                      action=lambda *a: fired.append(a))
        wd.initialize(trainer=None)
        wd.finalize()
        import time
        time.sleep(0.7)
        assert not fired

    def test_slow_but_progressing_extensions_do_not_fire(self):
        """An extension PASS longer than the timeout is fine as long as each
        individual unit beats the timeout (trainer.last_progress feeds the
        watchdog between units)."""
        import time

        from chainermn_tpu.extensions import Watchdog

        class FakeTrainer:
            last_progress = None

        fired = []
        tr = FakeTrainer()
        wd = Watchdog(timeout=0.3, poll_interval=0.05,
                      action=lambda *a: fired.append(a))
        wd.initialize(tr)
        wd.observe(tr)
        for _ in range(6):  # 0.9s total, each unit 0.15s < timeout
            time.sleep(0.15)
            tr.last_progress = time.monotonic()
        assert not fired
        wd.finalize()

    def test_disarmed_when_trainer_crashes(self, comm, tmp_path):
        """A raised step must stop the watcher thread (finalize_on_error):
        an armed watchdog would os._exit a process saving diagnostics."""
        import time

        from chainermn_tpu.extensions import Watchdog
        from chainermn_tpu.iterators import SerialIterator
        from chainermn_tpu.training import StandardUpdater, Trainer

        fired = []
        ds = [(np.zeros((2,), np.float32), 0)] * 16

        def exploding_step(state, batch):
            raise RuntimeError("boom at step 1")

        trainer = Trainer(
            StandardUpdater(SerialIterator(ds, 8, shuffle=False),
                            exploding_step, state=None),
            (2, "epoch"), out=str(tmp_path))
        wd = Watchdog(timeout=0.3, poll_interval=0.05,
                      action=lambda *a: fired.append(a))
        trainer.extend(wd)
        with pytest.raises(RuntimeError, match="boom"):
            trainer.run()
        time.sleep(0.6)  # past the timeout: a live watcher would have fired
        assert not fired
        assert wd._thread is None  # finalize_on_error stopped it

    def test_rejects_bad_timeout(self):
        from chainermn_tpu.extensions import Watchdog

        with pytest.raises(ValueError):
            Watchdog(timeout=0)

    def test_composes_with_trainer(self, comm, tmp_path):
        """A real (fast) training run with a generous watchdog: no fire."""
        from chainermn_tpu.extensions import Watchdog

        fired = []
        from chainermn_tpu.iterators import SerialIterator
        from chainermn_tpu.training import StandardUpdater, Trainer

        ds = [(np.zeros((2,), np.float32), 0)] * 16

        def step_fn(state, batch):
            return state, {"loss": 0.0}

        it = SerialIterator(ds, 8, shuffle=False)
        trainer = Trainer(StandardUpdater(it, step_fn, state=None),
                          (2, "epoch"), out=str(tmp_path))
        trainer.extend(Watchdog(timeout=60.0,
                                action=lambda *a: fired.append(a)))
        trainer.run()
        assert not fired


class TestExceptHook:
    def test_install_remove_and_passthrough(self):
        orig = sys.excepthook
        global_except_hook.add_hook()
        assert sys.excepthook is not orig
        global_except_hook.add_hook()  # idempotent
        # Single process: delegates to the original hook (no abort).
        try:
            raise ValueError("boom")
        except ValueError:
            info = sys.exc_info()
        global_except_hook._global_except_hook(*info)  # must not os._exit
        global_except_hook.remove_hook()
        assert sys.excepthook is orig


class TestReshardCheckpoint:
    """Offline world-resize tool: a checkpoint saved at world size 2
    becomes resumable at world size 1 (or any N) by duplicating the
    replicated shard."""

    def _write_shard(self, tmp_path, name, it, proc, nproc, state):
        import pickle

        fn = tmp_path / f"{name}.iter{it:012d}.proc{proc}of{nproc}"
        fn.write_bytes(pickle.dumps(state))

    def test_reshard_then_maybe_load(self, tmp_path):
        from chainermn_tpu.extensions import (create_multi_node_checkpointer,
                                              reshard_checkpoint)

        # a 2-process world saved generations 5 and 9 (replicated payloads)
        for it in (5, 9):
            for p in range(2):
                self._write_shard(tmp_path, "job", it, p, 2,
                                  {"w": [1.0, 2.0], "iteration": it})
        it = reshard_checkpoint(str(tmp_path), "job", new_nproc=1)
        assert it == 9
        # this process (world size 1) can now resume
        comm = mn.create_communicator("xla")
        cp = create_multi_node_checkpointer("job", comm, path=str(tmp_path))
        loaded, resumed = cp.maybe_load({"w": None, "iteration": -1})
        assert resumed == 9
        assert loaded == {"w": [1.0, 2.0], "iteration": 9}
        cp.finalize()

    def test_picks_requested_iteration_and_source(self, tmp_path):
        from chainermn_tpu.extensions import reshard_checkpoint

        for p in range(2):
            self._write_shard(tmp_path, "job", 5, p, 2, {"proc": p})
        it = reshard_checkpoint(str(tmp_path), "job", new_nproc=3,
                                iteration=5, source_process=1)
        assert it == 5
        import pickle
        for p in range(3):
            fn = tmp_path / f"job.iter{5:012d}.proc{p}of3"
            assert pickle.loads(fn.read_bytes()) == {"proc": 1}

    def test_same_iteration_two_world_sizes_raises_without_explicit(
            self, tmp_path):
        """Iteration 5 complete under BOTH world sizes 1 and 2: auto-pick
        would silently decide which payload wins — demand iteration=."""
        from chainermn_tpu.extensions import reshard_checkpoint

        self._write_shard(tmp_path, "job", 5, 0, 1, {"world": 1})
        for p in range(2):
            self._write_shard(tmp_path, "job", 5, p, 2, {"world": 2})
        with pytest.raises(RuntimeError, match="multiple world sizes"):
            reshard_checkpoint(str(tmp_path), "job", new_nproc=1)
        # explicit iteration confirms; largest world size wins, documented
        assert reshard_checkpoint(str(tmp_path), "job", new_nproc=1,
                                  iteration=5) == 5

    def test_incomplete_generation_rejected(self, tmp_path):
        from chainermn_tpu.extensions import reshard_checkpoint

        self._write_shard(tmp_path, "job", 5, 0, 2, {})  # proc 1 of 2 missing
        with pytest.raises(RuntimeError, match="no complete generation"):
            reshard_checkpoint(str(tmp_path), "job", new_nproc=1)

    def test_bad_source_process_rejected(self, tmp_path):
        from chainermn_tpu.extensions import reshard_checkpoint

        for p in range(2):
            self._write_shard(tmp_path, "job", 5, p, 2, {})
        with pytest.raises(ValueError, match="source_process"):
            reshard_checkpoint(str(tmp_path), "job", new_nproc=1,
                               source_process=5)

    def test_validates_new_nproc_and_ignores_stray_shards(self, tmp_path):
        from chainermn_tpu.extensions import reshard_checkpoint

        for p in range(2):
            self._write_shard(tmp_path, "job", 5, p, 2, {"ok": True})
        # stray out-of-range shard must not disqualify the generation
        self._write_shard(tmp_path, "job", 5, 7, 2, {"stray": True})
        with pytest.raises(ValueError, match="new_nproc"):
            reshard_checkpoint(str(tmp_path), "job", new_nproc=0)
        with pytest.raises(ValueError, match="source_process"):
            reshard_checkpoint(str(tmp_path), "job", new_nproc=1,
                               source_process=-1)
        assert reshard_checkpoint(str(tmp_path), "job", new_nproc=1) == 5


class TestMultiNodeSnapshot:
    """Replica-set snapshots (reference merged-era multi_node_snapshot):
    one shard per replica GROUP, restore fanned out within the group."""

    def _state(self, step):
        return {"w": np.full((2, 2), float(step)), "step": step}

    def test_roundtrip_writes_one_shard_per_group(self, comm, tmp_path):
        from chainermn_tpu.extensions import multi_node_snapshot

        cp = create_multi_node_checkpointer("job", comm, path=str(tmp_path))
        half = comm.size // 2
        snap = multi_node_snapshot(
            comm, cp, [list(range(half)), list(range(half, comm.size))])
        assert snap.maybe_load()[1] is None  # fresh start: no-op
        snap.save(self._state(3), iteration=3)
        snap.save(self._state(8), iteration=8)
        snap.flush()  # saves ride the one-deep async writer
        import os
        files = [f for f in os.listdir(tmp_path)
                 if not f.startswith(".") and "manifest" not in f]
        # 2 replica sets x 2 generations — NOT comm.size shards per gen
        # (plus one v2 manifest sidecar per generation, filtered above)
        assert len(files) == 4, files
        assert all(".set" in f and f"of2" in f for f in files)
        loaded, it = snap.maybe_load()
        assert it == 8
        np.testing.assert_array_equal(loaded["w"], np.full((2, 2), 8.0))

    def test_unlisted_ranks_become_singletons(self, comm, tmp_path):
        from chainermn_tpu.extensions import multi_node_snapshot

        cp = create_multi_node_checkpointer("job", comm, path=str(tmp_path))
        snap = multi_node_snapshot(comm, cp, [[0, 1]])
        # sets: [0,1] plus a singleton per remaining rank
        assert len(snap.sets) == comm.size - 1
        snap.save(self._state(1), iteration=1)
        snap.flush()  # saves ride the one-deep async writer
        import os
        files = [f for f in os.listdir(tmp_path)
                 if not f.startswith(".") and "manifest" not in f]
        assert len(files) == comm.size - 1

    def test_overlapping_sets_rejected(self, comm, tmp_path):
        from chainermn_tpu.extensions import multi_node_snapshot

        cp = create_multi_node_checkpointer("job", comm, path=str(tmp_path))
        with pytest.raises(ValueError):
            multi_node_snapshot(comm, cp, [[0, 1], [1, 2]])

    def test_gc_keeps_newest_generations(self, comm, tmp_path):
        from chainermn_tpu.extensions import multi_node_snapshot

        cp = create_multi_node_checkpointer(
            "job", comm, gc_interval=3, keep=2, path=str(tmp_path),
            async_write=False)
        snap = multi_node_snapshot(comm, cp, [list(range(comm.size))])
        for it in range(1, 7):
            snap.save(self._state(it), iteration=it)
        import os
        gens = sorted({int(f.split(".iter")[1][:12])
                       for f in os.listdir(tmp_path)
                       if not f.startswith(".")})
        assert len(gens) <= 3 and gens[-1] == 6, gens  # keep=2 (+pre-GC)

    def test_layout_change_fails_loudly(self, comm, tmp_path):
        from chainermn_tpu.extensions import multi_node_snapshot

        cp = create_multi_node_checkpointer("job", comm, path=str(tmp_path),
                                            async_write=False)
        old = multi_node_snapshot(comm, cp, [list(range(comm.size))])
        old.save(self._state(5), iteration=5)
        # resume under a DIFFERENT replica layout: shards exist but none
        # match — must raise, never silently fresh-start
        new = multi_node_snapshot(
            comm, cp, [[r] for r in range(comm.size)])
        with pytest.raises(RuntimeError, match="stale"):
            new.maybe_load()

    def test_async_save_rides_checkpointer_writer(self, comm, tmp_path):
        from chainermn_tpu.extensions import multi_node_snapshot

        cp = create_multi_node_checkpointer("job", comm, path=str(tmp_path),
                                            async_write=True)
        snap = multi_node_snapshot(comm, cp, [list(range(comm.size))])
        snap.save(self._state(2), iteration=2)
        snap.flush()
        loaded, it = snap.maybe_load()
        assert it == 2 and loaded["step"] == 2


# ---------------------------------------------------------------------------
# ISSUE 8: format-v2 manifests, torn-shard tolerance, elastic resume,
# bounded-grace preemption
# ---------------------------------------------------------------------------

class TestManifestV2:
    """Per-generation manifest: schema, layout, logical shapes, CRCs."""

    def _state(self, step):
        return {"w": np.full((2, 2), float(step)), "step": step}

    def test_manifest_written_and_checksums_match(self, comm, tmp_path):
        import json
        import zlib

        cp = create_multi_node_checkpointer("job", comm, path=str(tmp_path))
        cp.save(self._state(4), iteration=4)
        cp.flush()
        man_path = cp._manifest_path(4)
        assert os.path.exists(man_path)
        with open(man_path) as f:
            man = json.load(f)
        from chainermn_tpu.extensions import MANIFEST_SCHEMA
        assert man["schema"] == MANIFEST_SCHEMA
        assert man["world_size"] == 1
        shard = open(cp._filename(4), "rb").read()
        assert man["checksums"]["0"] == zlib.crc32(shard) & 0xFFFFFFFF
        # logical leaf shapes recorded (all replicated here)
        shapes = sorted(tuple(l["shape"]) for l in man["leaves"])
        assert shapes == [(), (2, 2)]

    def test_torn_shard_falls_back_to_previous_generation(self, comm,
                                                          tmp_path):
        """A truncated shard (death mid-write) is excluded by its CRC —
        resume lands on the previous consistent generation instead of
        unpickling garbage."""
        cp = create_multi_node_checkpointer("job", comm, path=str(tmp_path))
        cp.save(self._state(1), iteration=1)
        cp.save(self._state(2), iteration=2)
        cp.flush()
        shard2 = cp._filename(2)
        data = open(shard2, "rb").read()
        with open(shard2, "wb") as f:
            f.write(data[: len(data) // 2])  # torn write
        loaded, it = cp.maybe_load()
        assert it == 1
        np.testing.assert_array_equal(loaded["w"], np.full((2, 2), 1.0))

    def test_torn_only_generation_raises_loudly(self, comm, tmp_path):
        cp = create_multi_node_checkpointer("job", comm, path=str(tmp_path))
        cp.save(self._state(1), iteration=1)
        cp.flush()
        with open(cp._filename(1), "ab") as f:
            f.write(b"garbage appended after the atomic rename")
        with pytest.raises(RuntimeError, match="torn|restorable"):
            cp.maybe_load()

    def test_manifest_false_keeps_v1_behavior(self, comm, tmp_path):
        cp = create_multi_node_checkpointer("job", comm, path=str(tmp_path),
                                            manifest=False)
        cp.save(self._state(3), iteration=3)
        cp.flush()
        assert not os.path.exists(cp._manifest_path(3))
        assert cp.maybe_load()[1] == 3

    def test_writer_error_reraises_at_next_save(self, comm, tmp_path):
        """The async save thread's failure must surface at the NEXT
        checkpoint call, never vanish (ISSUE 8 satellite)."""
        cp = create_multi_node_checkpointer("job", comm, path=str(tmp_path))
        cp.save(self._state(1), iteration=1)
        cp.flush()
        cp._submit(lambda: (_ for _ in ()).throw(OSError("disk gone")))
        with pytest.raises(OSError, match="disk gone"):
            cp.save(self._state(2), iteration=2)
        # the checkpointer stays usable afterwards
        cp.save(self._state(3), iteration=3)
        assert cp.maybe_load()[1] == 3


class TestElasticResume:
    """maybe_load on a DIFFERENT process count: shards re-partitioned
    host-side per the manifest layout (reshard_host)."""

    def _old_world(self, tmp_path, old_n, iteration, name="job",
                   sharded_len=8):
        """Write a complete old-world generation + v2 manifest by hand:
        replicated w, axis-0-sharded m, per_rank rank_tag."""
        import json
        import pickle
        import zlib

        import jax

        from chainermn_tpu.extensions.checkpoint import (
            MANIFEST_SCHEMA, _leaf_paths_and_shapes)

        full_m = np.arange(sharded_len, dtype=np.float32)
        block = sharded_len // old_n
        checksums = {}
        state0 = None
        for p in range(old_n):
            state = {"m": full_m[p * block:(p + 1) * block],
                     "rank_tag": p,
                     "w": np.full((2, 2), 7.0)}
            state0 = state0 or state
            payload = pickle.dumps(state, protocol=pickle.HIGHEST_PROTOCOL)
            fn = tmp_path / f"{name}.iter{iteration:012d}.proc{p}of{old_n}"
            fn.write_bytes(payload)
            checksums[str(p)] = zlib.crc32(payload) & 0xFFFFFFFF
        # layout keyed by keystr dotted paths, like the checkpointer writes
        paths = [jax.tree_util.keystr(kp) for kp, _ in
                 jax.tree_util.tree_flatten_with_path(state0)[0]]
        m_key = next(p for p in paths if "m" in p and "rank" not in p)
        tag_key = next(p for p in paths if "rank_tag" in p)
        layout = {m_key: ["sharded", 0], tag_key: "per_rank"}
        man = {"schema": MANIFEST_SCHEMA, "name": name,
               "iteration": iteration, "world_size": old_n, "kind": "proc",
               "layout": layout,
               "leaves": _leaf_paths_and_shapes(state0, layout, old_n),
               "checksums": checksums}
        (tmp_path / f"{name}.iter{iteration:012d}.world{old_n}"
         ".manifest.json").write_text(json.dumps(man))
        return full_m

    def test_resume_from_larger_world(self, comm, tmp_path):
        full_m = self._old_world(tmp_path, old_n=2, iteration=6)
        cp = create_multi_node_checkpointer("job", comm, path=str(tmp_path))
        loaded, it = cp.maybe_load()
        assert it == 6
        np.testing.assert_array_equal(loaded["w"], np.full((2, 2), 7.0))
        # world 1 holds the WHOLE re-concatenated sharded leaf
        np.testing.assert_array_equal(loaded["m"], full_m)
        assert loaded["rank_tag"] == 0  # new rank 0 inherits old rank 0

    def test_newer_elastic_generation_beats_same_world(self, comm,
                                                       tmp_path):
        cp = create_multi_node_checkpointer("job", comm, path=str(tmp_path))
        cp.save({"m": np.zeros(8, np.float32), "rank_tag": 0,
                 "w": np.full((2, 2), 1.0)}, iteration=3)
        cp.flush()
        self._old_world(tmp_path, old_n=2, iteration=9)
        loaded, it = cp.maybe_load()
        assert it == 9
        np.testing.assert_array_equal(loaded["w"], np.full((2, 2), 7.0))

    def test_same_world_wins_when_newer(self, comm, tmp_path):
        self._old_world(tmp_path, old_n=2, iteration=3)
        cp = create_multi_node_checkpointer("job", comm, path=str(tmp_path))
        cp.save({"m": np.zeros(8, np.float32), "rank_tag": 0,
                 "w": np.full((2, 2), 1.0)}, iteration=5)
        cp.flush()
        loaded, it = cp.maybe_load()
        assert it == 5
        np.testing.assert_array_equal(loaded["w"], np.full((2, 2), 1.0))

    def test_torn_old_world_shard_disqualifies_generation(self, comm,
                                                          tmp_path):
        self._old_world(tmp_path, old_n=2, iteration=6)
        shard = tmp_path / "job.iter000000000006.proc1of2"
        shard.write_bytes(shard.read_bytes()[:10])  # torn
        cp = create_multi_node_checkpointer("job", comm, path=str(tmp_path))
        with pytest.raises(RuntimeError, match="restorable"):
            cp.maybe_load()

    def test_elastic_false_ignores_other_worlds(self, comm, tmp_path):
        self._old_world(tmp_path, old_n=2, iteration=6)
        cp = create_multi_node_checkpointer("job", comm, path=str(tmp_path))
        with pytest.raises(RuntimeError, match="world size"):
            cp.maybe_load(elastic=False)

    def test_gc_reaps_old_world_after_elastic_resume(self, comm, tmp_path):
        """Old-world shards have no owning process in the new world —
        without the other-world sweep an n=2→n=1 resume would leak
        proc1of2 (and the world2 manifest) forever."""
        self._old_world(tmp_path, old_n=2, iteration=6)
        cp = create_multi_node_checkpointer(
            "job", comm, gc_interval=1, path=str(tmp_path))
        loaded, it = cp.maybe_load()
        assert it == 6
        cp.save({"m": np.zeros(8, np.float32), "rank_tag": 0,
                 "w": np.full((2, 2), 1.0)}, iteration=7)
        cp.flush()
        left = sorted(os.listdir(tmp_path))
        assert not any("of2" in f or "world2" in f for f in left), left
        assert cp.maybe_load()[1] == 7  # new-world generation survives


class TestPreemptionHandler:
    """SIGTERM → flag → step-boundary save → bundle → exit 0, bounded by
    the grace deadline."""

    def _handler(self, tmp_path, comm=None, grace_s=30.0, **kw):
        import signal as _signal

        from chainermn_tpu.extensions.preemption import PreemptionHandler

        exits = []
        h = PreemptionHandler(
            create_multi_node_checkpointer(
                "job", comm, path=str(tmp_path / "ckpt"))
            if comm is not None else None,
            grace_s=grace_s, dump_dir=str(tmp_path / "dump"),
            exit_fn=exits.append, **kw)
        return h, exits, _signal

    def test_signal_sets_flag_only(self, comm, tmp_path):
        h, exits, signal = self._handler(tmp_path, comm)
        assert not h.requested
        h._on_signal(signal.SIGTERM, None)
        assert h.requested and not h.completed
        assert exits == []  # nothing exits until a step boundary

    def test_finish_saves_books_dumps_and_exits_zero(self, comm, tmp_path):
        from chainermn_tpu.extensions.preemption import PreemptionExit
        from chainermn_tpu.observability.flight import read_bundle
        from chainermn_tpu.observability.slo import GoodputLedger

        ledger = GoodputLedger()
        h, exits, signal = self._handler(tmp_path, comm, ledger=ledger)
        h._on_signal(signal.SIGTERM, None)
        state = {"w": np.arange(4.0)}
        with pytest.raises(PreemptionExit) as ei:
            h.check(state, iteration=11)
        assert ei.value.code == 0
        assert ei.value.generation == 11
        assert h.completed
        # the final generation is on disk and resumable
        loaded, it = h.checkpointer.maybe_load()
        assert it == 11
        np.testing.assert_array_equal(loaded["w"], np.arange(4.0))
        # save overhead booked, not vanished
        assert ledger.buckets()["checkpoint"] > 0
        # the preempt bundle names signal, grace, generation
        bundles = os.listdir(tmp_path / "dump")
        assert len(bundles) == 1 and "-preempt" in bundles[0]
        bundle = read_bundle(str(tmp_path / "dump" / bundles[0]))
        extra = bundle["manifest"]["extra"]["preempt"]
        assert extra["signal"] == "SIGTERM"
        assert extra["generation_saved"] == 11
        assert extra["why_not_saved"] is None
        assert extra["grace_used_s"] <= h.grace_s
        assert "resume" in extra["resume_hint"]

    def test_grace_deadline_bounds_a_wedged_step(self, comm, tmp_path):
        """No step boundary inside the grace window: the watchdog thread
        dumps a bundle explaining why nothing was saved and exits 0."""
        import time as _time

        from chainermn_tpu.observability.flight import read_bundle

        h, exits, signal = self._handler(tmp_path, comm, grace_s=0.3)
        h._on_signal(signal.SIGTERM, None)
        deadline = _time.monotonic() + 5.0
        while not exits and _time.monotonic() < deadline:
            _time.sleep(0.02)
        assert exits == [0], "deadline thread must exit 0, bounded"
        bundles = os.listdir(tmp_path / "dump")
        assert len(bundles) == 1
        extra = read_bundle(
            str(tmp_path / "dump" / bundles[0]))["manifest"]["extra"]
        assert "grace budget exhausted" in extra["preempt"]["why_not_saved"]
        assert extra["preempt"]["generation_saved"] is None

    def test_no_checkpointer_still_bounded_exit_zero(self, tmp_path):
        from chainermn_tpu.extensions.preemption import (PreemptionExit,
                                                         PreemptionHandler)

        exits = []
        h = PreemptionHandler(None, grace_s=5.0,
                              dump_dir=str(tmp_path / "dump"),
                              exit_fn=exits.append)
        import signal
        h._on_signal(signal.SIGTERM, None)
        with pytest.raises(PreemptionExit) as ei:
            h.check({"x": 1}, iteration=2)
        assert ei.value.code == 0 and ei.value.generation is None

    def test_save_failure_still_exits_zero_with_reason(self, comm,
                                                       tmp_path):
        from chainermn_tpu.extensions.preemption import PreemptionExit
        from chainermn_tpu.observability.flight import read_bundle

        h, exits, signal = self._handler(tmp_path, comm)
        h._on_signal(signal.SIGTERM, None)
        with pytest.raises(PreemptionExit) as ei:
            h.check({"bad": lambda: None}, iteration=4)  # unpicklable
        assert ei.value.code == 0 and ei.value.generation is None
        bundles = os.listdir(tmp_path / "dump")
        extra = read_bundle(
            str(tmp_path / "dump" / bundles[0]))["manifest"]["extra"]
        assert "save failed" in extra["preempt"]["why_not_saved"]

    def test_rejects_nonpositive_grace(self):
        from chainermn_tpu.extensions.preemption import PreemptionHandler

        with pytest.raises(ValueError, match="grace_s"):
            PreemptionHandler(None, grace_s=0)

    def test_install_uninstall_restores_disposition(self, tmp_path):
        import signal

        from chainermn_tpu.extensions.preemption import PreemptionHandler

        prev = signal.getsignal(signal.SIGTERM)
        h = PreemptionHandler(None, dump_dir=str(tmp_path))
        h.install()
        assert signal.getsignal(signal.SIGTERM) == h._on_signal
        h.install()  # idempotent
        h.uninstall()
        assert signal.getsignal(signal.SIGTERM) == prev
