"""CPU rehearsal of ``chip_smoke.py`` and the compile-cache rule.

The smoke has no CPU path: ``main(..., _allow_cpu=True, _sizes=TINY)`` is
the test-only override these tests (and nothing else) use to walk its
control flow at tiny widths — every phase, the JSON lines, the shape of
the last line.  What only the chip can show (compiled kernels, real
widths, times) is ``python chip_smoke.py`` through the chip tool;
``tests/test_chip_compile.py`` covers what the chip's compiler accepts.
"""

import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TINY = {
    "parity": {"flash": (1, 128, 2, 64), "decode": (1, 64, 2, 16),
               "conv": (2, 8, 8, 128)},
    "resnet": {"arch": "resnet18", "image": 32, "batch": 2, "steps": 2,
               "classes": 10},
    "lm": {"vocab": 256, "d_model": 64, "n_layers": 2, "n_heads": 2,
           "seq": 128, "batch": 4, "steps": 3},
    "serve": {"n_slots": 2, "prompt": 8, "new": 4, "requests": 4,
              "train_steps": 1},
    "cross": {"arch": "resnet18", "image": 32, "global_batch": 16,
              "steps": 3, "classes": 10, "lm_steps": 2},
}


def _device_row():
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def _rows(capsys):
    out = capsys.readouterr().out
    lines = [ln for ln in out.splitlines() if ln.strip()]
    return [json.loads(ln) for ln in lines]    # stdout is JSON lines ONLY


def test_rehearsal_one_chip_phases_and_last_line(capsys):
    rc = chip_smoke.main([], _allow_cpu=True, _sizes=TINY)
    rows = _rows(capsys)
    assert rc == 0
    assert [r.get("phase") for r in rows[:-1]] == [
        "setup", "kernel-parity", "train-resnet50", "train-lm", "serve",
        "compile-cache"]
    # the last line: exactly ok + the device as JAX reports it
    assert rows[-1] == {"ok": True, "device": _device_row()}
    by = {r["phase"]: r for r in rows[:-1]}
    assert by["setup"]["compile_cache_dir"]
    assert by["setup"]["native_prefetcher"] in (True, False)
    for phase in ("train-resnet50", "train-lm"):
        r = by[phase]
        assert r["first_loss"] != r["last_loss"]
        assert r["compile_s"] >= 0 and r["run_s"] >= 0
        assert "tpu_custom_call" in r and "peak_bytes_in_use" in r
    serve = by["serve"]
    assert serve["statuses"] == ["done"] * TINY["serve"]["requests"]
    assert serve["token_exact_request"] == TINY["serve"]["requests"] - 1
    assert 0.0 < serve["goodput_coverage_frac"] <= 1.0
    assert set(serve["tpu_custom_call"]) == {"tick", "prefill",
                                            "greedy_decode"}


def test_rehearsal_four_chips_runs_only_the_cross_chip_phase(capsys):
    rc = chip_smoke.main(["--chips", "4", "--seed", "1"], _allow_cpu=True,
                         _sizes=TINY)
    rows = _rows(capsys)
    assert rc == 0
    assert [r.get("phase") for r in rows[:-1]] == [
        "setup", "cross-collectives", "cross-dp-resnet", "cross-dpxtp-lm",
        "compile-cache"]
    assert rows[-1] == {"ok": True, "device": _device_row()}
    by = {r["phase"]: r for r in rows[:-1]}
    assert by["cross-collectives"]["chips"] == 4
    assert by["cross-dpxtp-lm"]["mesh"] == [2, 2]
    assert by["cross-dpxtp-lm"]["n_model_sharded_params"] > 0


def test_refuses_the_cpu_without_the_override(capsys):
    assert chip_smoke.main([]) != 0
    captured = capsys.readouterr()
    assert captured.out == ""              # prints no result
    assert "no TPU" in captured.err


def test_script_exits_nonzero_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       cwd=REPO, env=env, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_a_failing_phase_is_never_carried_past(capsys, monkeypatch):
    def boom(ctx):
        raise RuntimeError("phase failed")

    ran = []
    monkeypatch.setattr(chip_smoke, "ONE_CHIP_PHASES",
                        (boom, lambda ctx: ran.append("later phase")))
    with pytest.raises(RuntimeError, match="phase failed"):
        chip_smoke.main([], _allow_cpu=True, _sizes=TINY)
    assert ran == []                       # nothing runs after a failure
    assert '"ok"' not in capsys.readouterr().out


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_env_set_means_code_sets_nothing(
        monkeypatch, tmp_path, cache_dir_config):
    from chainermn_tpu.topology import enable_compile_cache

    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None   # untouched


def test_compile_cache_unset_means_fixed_in_checkout_path(
        monkeypatch, cache_dir_config):
    from chainermn_tpu.topology import enable_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert enable_compile_cache() == want  # fixed: no pid, time, temp name
