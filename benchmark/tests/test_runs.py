"""Each driver end to end through ``main(_allow_cpu=...)`` at tiny sizes
(interpret-mode kernels), the contract's last line, and ``correct`` coming
out false when the timed path is broken underneath or the precision is
lowered."""

import json
import time

import pytest

from benchmark import control, run
from benchmark.harness import loader
from conftest import (TINY_GPT2, TINY_IMAGES, TINY_RESNET, TINY_SERVE,
                      TINY_TRAIN)

KEYS = {"correct", "attempted", "failed", "metrics", "device"}
LM_TRAIN, LM_SERVE, IMG_TRAIN = ("gpt2-medium-train-s1024",
                                 "gpt2-medium-serve-steady",
                                 "resnet50-train-dp4")
SIZES = {LM_TRAIN: {"config": TINY_GPT2, "traffic": TINY_TRAIN},
         LM_SERVE: {"config": TINY_GPT2, "traffic": TINY_SERVE},
         IMG_TRAIN: {"config": TINY_RESNET, "traffic": TINY_IMAGES}}


def _run(capsys, cell, trace=0, seconds=2, seed=3_000_000_017):
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace)], _allow_cpu=True,
                  _sizes=SIZES[cell], _t0=time.perf_counter())
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    assert all(line.startswith("[cpu cpu x") for line in lines[:-1])
    return json.loads(lines[-1])


@pytest.fixture
def with_unproven_cells(monkeypatch):
    """``resnet50-train-dp4`` is not in ``workloads`` (PERF.md, Open
    questions) but its files are kept: put it into the manifest the loader
    sees, so that they stay exercised (four virtual CPU devices)."""
    man = loader.manifest()
    if IMG_TRAIN not in {w["name"] for w in man["workloads"]}:
        man["configs"].append({"name": "resnet50",
                               "file": "benchmark/configs/resnet50.json"})
        man["workloads"].append({"name": IMG_TRAIN, "config": "resnet50",
                                 "traffic": "image-train-dp", "chips": 4})
        for m in man["end_to_end"]:
            if m["name"] == "train_samples_per_s":
                m["workloads"].append(IMG_TRAIN)
        man["per_layer"] += [
            {"name": n, "unit": u, "moves": "train_samples_per_s",
             "workloads": [IMG_TRAIN]} for n, u in (
                ("input_wait_ms", "ms"), ("collective_ms_per_step", "ms"),
                ("collective_exposed_share", "%"))]
    monkeypatch.setattr(loader, "manifest", lambda: man)
    return man


@pytest.mark.parametrize("cell", [LM_TRAIN, LM_SERVE, IMG_TRAIN])
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_and_prints_the_contracts_last_line(
        capsys, with_unproven_cells, cell, trace):
    man = with_unproven_cells
    out = _run(capsys, cell, trace)
    assert set(out) == KEYS | ({"breakdown"} if trace else set())
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert out["device"]["platform"] == "cpu"
    group = "per_layer" if trace else "end_to_end"
    known = {m["name"]: m["unit"] for m in man[group]}
    assert out["metrics"], out
    for name, m in out["metrics"].items():
        assert m["unit"] == known[name] and isinstance(m["value"], float)
    if trace:
        assert {"busy_s", "window_s"} <= set(out["device"])
        assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert "setup_s" in out["metrics"] and len(out["metrics"]) >= 2


def _patched_family(monkeypatch, name, patch):
    real = loader.module

    def module(kind, mod_name):
        mod = real(kind, mod_name)
        if (kind, mod_name) == ("families", name):
            patch(mod)
        return mod

    monkeypatch.setattr(loader, "module", module)


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        capsys, monkeypatch):
    def patch(mod):
        import jax

        step = mod.Trainer.step

        def frozen(self):
            # the real step runs on copies (it donates its arguments), and
            # its new state is thrown away
            p, st = self.p, self.st
            self.p, self.st = jax.tree_util.tree_map(
                lambda a: a.copy(), (p, st))
            loss = step(self)
            self.p, self.st = p, st
            return loss

        mod.Trainer.step = frozen

    _patched_family(monkeypatch, "gpt2", patch)
    out = _run(capsys, LM_TRAIN)
    assert out["correct"] is False


def test_a_token_altered_where_it_is_produced_is_not_correct(
        capsys, monkeypatch):
    from chainermn_tpu.serving import frontend

    emit = frontend.ServingEngine._emit

    def wrong(self, req, token, now):
        emit(self, req, (token + 1) % 200 if len(req.tokens) % 3 == 2
             else token, now)

    monkeypatch.setattr(frontend.ServingEngine, "_emit", wrong)
    out = _run(capsys, LM_SERVE)
    assert out["correct"] is False


@pytest.mark.parametrize("cell", [LM_TRAIN, LM_SERVE])
def test_the_control_in_lower_precision_fails_a_limit(capsys, monkeypatch,
                                                      cell):
    # a widest gap scales with the model: the tiny one's readings are the
    # program 0 .. 1e-3 and the fp8 control 1.3e-2 .. 3.9e-2 (seeds 5, 6, 7),
    # so the tiny size gets its own limit between them; the training limits
    # hold at both sizes
    _patched_family(monkeypatch, "gpt2", lambda mod: mod.ref.LIMITS.update(
        served_logit_gap=5e-3))
    # the served-token gap is a widest gap: it wants as many tokens as a
    # run compares, so the tiny window is long and the whole of it compared
    sizes = loader.merge(SIZES[cell], {"traffic": {"check_requests": 64}})
    rc = control.main(["--workload", cell, "--seeds", "5,6,7", "--seconds",
                       "8"], _allow_cpu=True, _sizes=sizes)
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, lines[-1]          # no control passed
    assert json.loads(lines[-1])["controls_that_passed"] == 0
    for line in lines[:-1]:
        if "] control {" in line:
            rows = json.loads(line.split("] control ", 1)[1])["rows"]
            for r in rows:           # ... while the program itself does
                if r["name"].startswith("program."):
                    assert r["ok"], r


def test_the_resnet_reference_is_the_programs_model_in_float32():
    """Same weights, same images: the plain reference's training-mode loss
    is the flax model's, to float32 rounding."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chainermn_tpu.models.mlp import cross_entropy_loss
    from chainermn_tpu.models.resnet import ARCHS

    ref = loader.module("reference", "resnet")
    cfg = {"num_filters": 8, "image_size": 32, "num_classes": 10}
    v = ref.init_variables(jax.random.PRNGKey(3), cfg)
    rng = np.random.default_rng(0)
    x = rng.integers(0, 256, (16, 32, 32, 3), dtype=np.uint8)
    y = rng.integers(0, 10, 16).astype(np.int32)
    model = ARCHS["resnet50"](num_classes=10, num_filters=8, stem_strides=2,
                              dtype=jnp.float32)
    out, _ = model.apply(v, x.astype(np.float32) / 255.0 - 0.5, train=True,
                         mutable=["batch_stats"])
    want = float(ref.shard_loss(v["params"], jnp.asarray(x), jnp.asarray(y)))
    assert float(cross_entropy_loss(out, y)) == pytest.approx(want, rel=1e-4)
