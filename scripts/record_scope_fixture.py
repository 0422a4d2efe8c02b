#!/usr/bin/env python
"""Record ``benchmark/tests/recorded_v5e_scopes.xplane.pb`` on the chip.

A tiny two-layer latent-attention / routed-expert LM served through the
``ServingEngine`` (one dense and one expert layer: ``serving_tick`` and
``serving_prefill_128`` with every scope of the served vocabulary but the
delta-rule and window ones) and a tiny two-layer GPT-2-style ``train_step``,
a few executions of each inside one ``traced_slice``, with the python tracer
off.  What the profiler wrote is then SLIMMED to what the benchmark's readers
read (:func:`slim`: every plane, line, event and name stays; of an
operation's dozen stats the two the scope reader joins by, and none of the
compiled programs' HLO protos — 2.2 MB become 0.6).  The fixture is what
``benchmark/tests/test_scope_trace.py`` reads; re-record it when the scope
vocabulary (``benchmark/harness/scope_trace.py::BUCKETS``) changes:

    chiprun -- python scripts/record_scope_fixture.py [seed]   # -> chiprun_out/
"""

import importlib.util
import os
import shutil
import sys
from functools import partial

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

#: DeepSeek-V3's layer at widths the chip's kernels take (whole lane tiles)
CFG = {
    "hidden_size": 256, "num_hidden_layers": 2, "first_k_dense_replace": 1,
    "num_attention_heads": 2, "q_lora_rank": 128, "kv_lora_rank": 128,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "intermediate_size": 512, "moe_intermediate_size": 128,
    "n_routed_experts": 16, "n_routed_experts_held": 4, "n_group": 4,
    "topk_group": 2, "num_experts_per_tok": 4, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "vocab_size": 512, "rms_norm_eps": 1e-6,
    "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 128, "type": "yarn"},
}
ENGINE = dict(n_slots=8, max_total=512, prefill_bucket=128, queue_capacity=8,
              spill_bytes=0)
TRAIN = dict(vocab=512, d=128, heads=2, layers=2, seq=256, batch=2)


def _load(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "tests", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def build(devices, seed: int):
    """``(engine, train step, its arguments)`` on ``devices[:1]``, weights
    and tokens drawn from ``seed``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import PartitionSpec as P

    import chainermn_tpu as mn
    from chainermn_tpu.parallel import (init_tp_transformer_lm,
                                        make_hybrid_shard_map_step,
                                        tp_transformer_lm_loss,
                                        transformer_lm_specs)
    from chainermn_tpu.serving import ServingEngine

    fixture = _load("test_deepseek_serving")
    mesh = mn.make_nd_mesh(("model",), (1,), devices[:1])
    k_serve, k_train = jax.random.split(jax.random.PRNGKey(seed))
    params = fixture.ref.init_params(k_serve, CFG, jnp.bfloat16)
    eng = ServingEngine(params, head_dim=CFG["v_head_dim"], mesh=mesh,
                        arch=fixture.arch_of(CFG), **ENGINE)
    t = TRAIN
    mesh2 = mn.make_nd_mesh(("data", "model"), (1, 1), devices[:1])
    lm = init_tp_transformer_lm(k_train, t["vocab"], t["d"],
                                t["heads"], t["layers"], max_len=t["seq"])
    optimizer = optax.adamw(1e-3)
    step = make_hybrid_shard_map_step(
        partial(tp_transformer_lm_loss, head_dim=t["d"] // t["heads"],
                axis_name="model"),
        optimizer, mesh2, lm, transformer_lm_specs(lm, "model"),
        data_axis="data", batch_spec=P("data"))
    batch = (np.random.RandomState(seed).randint(
        0, t["vocab"], (t["batch"], t["seq"] + 1)).astype(np.int32),)
    return eng, step, [lm, optimizer.init(lm), batch]


def serve(eng, n: int, new: int, seed: int):
    """``n`` requests of ``new`` tokens to their end; ``seed`` draws the
    prompts (a round that repeated another's would hit the prefix cache
    and run no prefill)."""
    import numpy as np

    rng = np.random.RandomState(seed)
    handles = [eng.submit(rng.randint(1, CFG["vocab_size"], 40 + 30 * i),
                          new) for i in range(n)]
    while eng.scheduler.queue_depth or eng.pool.busy_count:
        eng.step()
    assert all(h.status == "done" for h in handles)


def _varint(x: int) -> bytes:
    out = bytearray()
    while x >= 0x80:
        out.append(x & 0x7F | 0x80)
        x >>= 7
    return bytes(out + bytes([x]))


def _put(field: int, kind: int, value) -> bytes:
    """One field of a protobuf message, as ``scope_trace._fields`` gave it."""
    if kind == 0:
        return _varint(field << 3) + _varint(value)
    value = bytes(value)
    return _varint(field << 3 | kind) + (
        _varint(len(value)) if kind == 2 else b"") + value


def slim(src: str, dst: str) -> None:
    """Copy the ``.xplane.pb`` ``src`` to ``dst`` without what no reader of
    the benchmark reads: of every event metadata's stats only ``tf_op`` and
    ``program_id`` stay (not the source stacks, shapes, byte counts and the
    ``/host:metadata`` plane's HLO protos), and of the host's lines only
    the python threads'.  Planes, device lines, events, names and times are
    byte for byte what the profiler wrote."""
    from benchmark.harness.scope_trace import _fields, _map_value, _text

    def message(buf):
        return {f: v for f, _, v in _fields(buf)}

    with open(src, "rb") as f:
        space = memoryview(f.read())
    out = bytearray()
    for field, kind, plane in _fields(space):
        parts = list(_fields(plane)) if field == 1 else []
        name = next((_text(v) for f, _, v in parts if f == 2), "")
        keep = {message(_map_value(v)).get(1) for f, _, v in parts if f == 5
                and _text(message(_map_value(v)).get(2, b""))
                in ("tf_op", "program_id")}
        slimmed = bytearray()
        for f, k, v in parts:
            if f == 3 and name == "/host:CPU" and not _text(
                    message(v).get(2, b"")).startswith("python"):
                continue
            if f == 4:          # one entry of the event_metadata map
                entry = message(v)
                v = _put(1, 0, entry[1]) + _put(2, 2, b"".join(
                    _put(mf, mk, mv) for mf, mk, mv in _fields(entry[2])
                    if mf != 3 and (mf != 5 or message(mv).get(1) in keep)))
            slimmed += _put(f, k, v)
        out += _put(field, kind, slimmed if field == 1 else plane)
    with open(dst, "wb") as f:
        f.write(out)


def main():
    import jax

    from benchmark.harness import scope_trace
    from benchmark.harness.trace_reduce import SLICE, find_xplane

    devices = jax.devices()
    print("devices", devices, flush=True)
    seed = int(sys.argv[1]) if len(sys.argv) > 1 else 35
    eng, step, state = build(devices, seed)
    serve(eng, 2, 4, seed)                            # compiles, warm
    state[0], state[1], loss = step(*state)
    print("warm loss", float(loss), flush=True)
    out = os.path.join(ROOT, "benchmark", ".scratch", "scope_fixture")
    shutil.rmtree(out, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=options)
    with jax.profiler.TraceAnnotation(SLICE):
        serve(eng, 2, 5, seed + 1)
        for _ in range(3):
            state[0], state[1], loss = step(*state)
        float(loss)
    jax.profiler.stop_trace()
    path = find_xplane(out)
    kept = os.path.join(ROOT, "chiprun_out", "recorded_v5e_scopes.xplane.pb")
    os.makedirs(os.path.dirname(kept), exist_ok=True)
    slim(path, kept)
    print("fixture", kept, os.path.getsize(kept), "bytes of",
          os.path.getsize(path), flush=True)
    read = {prefix: scope_trace.split(kept, prefix)
            for prefix in ("serving_tick", "serving_prefill", "train_step")}
    for prefix, got in read.items():
        print(prefix, "->", None if got is None else scope_trace.table(got),
              flush=True)
    return 0 if all(got is not None for got in read.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
