import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import loader, stats, trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "recorded_v5e_tiny.xplane.pb")


def test_loader_resolves_every_name_to_a_file():
    man = loader.manifest()
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"] for m in man["end_to_end"]}
    cells = {w["name"] for w in man["workloads"]}
    for w in man["workloads"]:
        cell = loader.cell(man, w["name"])
        assert os.path.isfile(os.path.join(
            loader.BENCH, "families", cell["config"]["family"] + ".py"))
        assert os.path.isfile(os.path.join(
            loader.BENCH, "reference", cell["config"]["family"] + ".py"))
        assert os.path.isfile(os.path.join(
            loader.BENCH, "drivers", cell["traffic"]["driver"] + ".py"))
        reported = {m["name"] for m in loader.metrics_of(
            man, "end_to_end", w["name"])}
        assert "setup_s" in reported and len(reported) >= 2
        assert loader.metrics_of(man, "per_layer", w["name"], reported)
    for m in man["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", ())) <= cells
        assert callable(loader.module("layer_metrics", m["name"]).read)
    with pytest.raises(KeyError):
        loader.cell(man, "no-such-cell")


def test_percentile_refuses_a_thin_tail():
    values = list(range(1, 201))
    assert stats.percentile(values, 95) == 190      # ten samples beyond it
    with pytest.raises(stats.TooFewSamples):
        stats.percentile(values[:199], 95)
    with pytest.raises(stats.TooFewSamples):
        stats.percentile([], 50)
    assert stats.percentile([3, 1, 2], 50, min_beyond=0) == 2
    assert stats.spread([10, 10, 10, 10, 11, 9]) == pytest.approx(0.05)


def test_trace_reduce_on_known_events():
    ev = {"devices": {0: [("while", 0, 100), ("fusion.1", 0, 40),
                          ("fusion.2", 50, 100), ("fusion.3", 150, 200)],
                      1: [("fusion.1", 0, 300)]},
          "async": {0: [("all-reduce-start.1", 40, 60)]},
          "host": [("traced_slice", 0, 300), ("input", 100, 150),
                   ("dispatch", 200, 290)]}
    r = tr.reduce(ev, ("input", "dispatch"))
    assert r["window_s"] == pytest.approx(300e-9)
    assert r["busy_s_per_chip"] == {0: pytest.approx(150e-9),
                                    1: pytest.approx(300e-9)}
    assert r["busy_s"] == pytest.approx(225e-9)      # mean over the chips
    assert r["op_seconds"]["while"] == pytest.approx(10e-9)  # self time
    assert r["device_ops"][0] == ["fusion.2", pytest.approx(50e-9)]
    assert dict(map(tuple, r["idle_gaps"])) == {
        "dispatch": pytest.approx(100e-9), "input": pytest.approx(50e-9)}
    assert r["collective_s"] == pytest.approx(20e-9)
    assert r["collective_exposed_s"] == pytest.approx(10e-9)  # 40..50 alone
    assert tr.reduce(ev, (), chips=1)["n_chips"] == 1


def test_trace_reduce_on_the_recorded_v5e_trace():
    """Four dispatches of one tanh(x @ x) fusion on a TPU v5e, traced by
    benchmark/.scratch/tiny_trace.py (my chip run, PR 24)."""
    r = tr.reduce_file(RECORDED, ("input", "dispatch", "readback"))
    assert r["n_chips"] == 1
    assert r["window_s"] == pytest.approx(0.01387319, rel=1e-6)
    assert r["busy_s"] == pytest.approx(3.568e-05, rel=1e-3)
    assert r["device_ops"][0][0] == "fusion"
    assert r["op_seconds"]["fusion"] == pytest.approx(3.5632e-05, rel=1e-3)
    idle = 1.0 - r["busy_s"] / r["window_s"]
    assert idle == pytest.approx(0.9974, abs=1e-3)
    gaps = dict(map(tuple, r["idle_gaps"]))
    assert set(gaps) <= {"input", "dispatch", "readback", "none"}
    assert gaps["input"] > 0.008          # four sleeps of 2 ms
    assert r["collective_s"] == 0.0


def test_short_name_tags_a_pallas_kernel():
    text = ('%jvp__.49 = (f32[8192,128]{1,0}) custom-call(bf16[8192,1024] '
            '%bitcast.2642), custom_call_target="tpu_custom_call", '
            'frontend_attributes={kernel_metadata={}}')
    assert tr.short_name(text) == "jvp__.49" + tr.KERNEL_TAG
    assert tr.short_name("%fusion.7 = (f32[1]) fusion(%custom-call.6)") \
        == "fusion.7"


def test_importing_the_benchmark_touches_no_device():
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "import benchmark.run, benchmark.sweep_rate\n"
        "from benchmark.harness import loader\n"
        "man = loader.manifest()\n"
        "for c in man['configs']:\n"
        "    fam = loader.cell(man, next(w['name'] for w in man['workloads']"
        " if w['config'] == c['name']))['config']['family']\n"
        "    loader.module('families', fam)\n"
        "for t in {w['traffic'] for w in man['workloads']}:\n"
        "    import json, os\n"
        "    d = json.load(open(os.path.join(loader.BENCH, 'traffic', t + '.json')))\n"
        "    loader.module('drivers', d['driver'])\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, xla_bridge._backends\n"
        "print('untouched')\n" % loader.ROOT)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    assert "untouched" in out.stdout
