"""The program's ``jax.named_scope`` vocabulary is a PARTITION (ISSUE 35).

``benchmark/harness/scope_trace.py`` books a traced program's device time to
the scope each operation was written under, so every equation of the served
programs and of the train step has to lie under a scope its ``BUCKETS`` table
names, and the attention half and the FFN half of a block must be told apart.
Walked here on the jaxprs (the scopes are the equations' name stacks: the same
strings the compiler writes into the HLO's ``op_name``), at the tiny sizes the
four ``tests/test_*_serving.py`` files build, with ``jax.default_backend``
steered to the TPU side so the kernel paths — the ones the chip runs — are the
ones walked (tracing a Pallas call needs no chip).
"""

import importlib.util
import json
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import chainermn_tpu as mn
from benchmark.harness.scope_trace import (BUCKETS, PHASE, bucket_of,
                                           leaf_of, scope_path)

HERE = os.path.dirname(os.path.abspath(__file__))
#: architecture -> the serving test file whose tiny model it is
FIXTURES = {"gpt2": None, "mla+moe": "test_deepseek_serving",
            "kda+mla+moe": "test_kimi_linear_serving",
            "window-gqa+moe": "test_laguna_serving"}
SERVED = {"cache_write", "attn_proj", "attn_core", "ffn_dense", "embed_head"}
EXPERTS = {"moe_route", "moe_experts"}
#: what an equation of an attention half, and of an FFN half, lies under
ATTENTION = ("tick/work_list", "cache_write", "block/attn/", "block/mla/",
             "block/kda/")
FFN = ("block/mlp", "block/moe/")


def _fixture(name):
    spec = importlib.util.spec_from_file_location(
        "scope_" + name, os.path.join(HERE, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def paths_of(jaxpr, prefix=""):
    """The scope path of every equation, sub-jaxprs walked in place of the
    equation that holds them (a Pallas kernel's body is one operation)."""
    for eqn in jaxpr.eqns:
        path = prefix + str(eqn.source_info.name_stack)
        inner = []
        if eqn.primitive.name != "pallas_call":
            for value in eqn.params.values():
                for x in (value if isinstance(value, (tuple, list))
                          else (value,)):
                    x = getattr(x, "jaxpr", x)
                    if hasattr(x, "eqns"):
                        inner.append(x)
        if inner:
            for sub in inner:
                yield from paths_of(sub, path + "/")
        else:
            yield f"{path}/{eqn.primitive.name}"


def _served_programs(arch_name, devices, **engine_kw):
    from chainermn_tpu.serving import ServingEngine
    from chainermn_tpu.serving.engine import result_size

    mesh = mn.make_nd_mesh(("model",), (1,), devices[:1])
    if FIXTURES[arch_name] is None:
        params = mn.parallel.init_tp_transformer_lm(
            jax.random.PRNGKey(0), 32, 16, 4, 2, max_len=64)
        eng = ServingEngine(params, head_dim=4, mesh=mesh, n_slots=4,
                            max_total=48, prefill_bucket=8, queue_capacity=8,
                            spill_bytes=0)
    else:
        mod = _fixture(FIXTURES[arch_name])
        eng = mod._engine(mod.ref.init_params(jax.random.PRNGKey(3), mod.CFG,
                                              jnp.float32), mesh, **engine_kw)
    dec, n = eng.engine, eng.pool.n_slots
    caches = eng.pool.read(lambda c: c)
    tick = jax.make_jaxpr(dec._tick_prog)(
        dec._params, caches, np.zeros(result_size(dec.arch, n), np.int32),
        np.zeros(n, np.int32), np.zeros(n, np.int32),
        np.zeros((n, 2), np.uint32), np.zeros(n, np.float32),
        np.ones(n, bool))
    prefill = jax.make_jaxpr(dec._build_prefill(16))(
        dec._params, caches, np.zeros((1, 16), np.int32), jnp.int32(9),
        jnp.int32(1), np.zeros(2, np.uint32), jnp.float32(0))
    return {"serving_tick": tick, "serving_prefill": prefill}


def _train_step(devices):
    import optax

    from chainermn_tpu.parallel import (init_tp_transformer_lm,
                                        make_hybrid_shard_map_step,
                                        tp_transformer_lm_loss,
                                        transformer_lm_specs)

    mesh = mn.make_nd_mesh(("data", "model"), (1, 1), devices[:1])
    params = init_tp_transformer_lm(jax.random.PRNGKey(0), 32, 16, 4, 2,
                                    max_len=16)
    optimizer = optax.adamw(1e-3)
    step = make_hybrid_shard_map_step(
        partial(tp_transformer_lm_loss, head_dim=4, axis_name="model"),
        optimizer, mesh, params, transformer_lm_specs(params, "model"),
        data_axis="data", batch_spec=P("data"), donate=False)
    return jax.make_jaxpr(step)(params, optimizer.init(params),
                                (np.zeros((2, 17), np.int32),))


CASES = [(a, p) for a in FIXTURES for p in ("serving_tick", "serving_prefill")
         ] + [("gpt2", "train_step")]


@pytest.fixture(scope="module")
def programs(devices):
    """Each architecture's programs traced once, on first use."""
    traced = {}

    def get(arch_name, program, monkeypatch):
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        if program == "train_step":
            return _train_step(devices)
        if arch_name not in traced:
            traced[arch_name] = _served_programs(arch_name, devices)
        return traced[arch_name][program]

    return get


@pytest.mark.parametrize("arch_name,program", CASES,
                         ids=[f"{a}-{p}" for a, p in CASES])
def test_every_equation_lies_in_one_named_bucket(programs, monkeypatch,
                                                 arch_name, program):
    paths = list(paths_of(programs(arch_name, program, monkeypatch).jaxpr))
    assert len(paths) > 100
    unscoped = [p for p in paths if bucket_of(p) is None]
    assert not unscoped, unscoped[:10]
    found = {bucket_of(p) for p in paths}
    if program == "train_step":
        assert found == {"fwd", "bwd", "optimizer"}
        leaves = {leaf_of(p) for p in paths}
        for phase in PHASE:     # each phase is split by the block's scopes
            assert {(phase, s) for s in ("embed", "block/attn", "block/mlp",
                                         "head_ce")} <= leaves
        return
    want = SERVED | (EXPERTS if "moe" in arch_name else set())
    assert found == want
    scopes = {leaf_of(p)[1] for p in paths}
    assert ("tick/work_list" in scopes) == (program == "serving_tick")
    if "moe" in arch_name:
        assert {"block/moe/route", "block/moe/dispatch", "block/moe/gmm",
                "block/moe/shared"} <= scopes
    if "kda" in arch_name:
        assert {"block/kda/conv", "block/kda/gate", "block/kda/state_update",
                "block/kda/proj", "block/mla/core"} <= scopes
    if "window" in arch_name:
        assert "block/attn/gate" in scopes
        assert any("/block/attn/window/" in scope_path(p) for p in paths)
    # the halves of a block are told apart: nothing of an FFN half lies
    # under an attention scope (``tick/attn`` wrapped both until PR 35)
    for p in paths:
        path = scope_path(p)
        assert "/tick/attn/" not in path, p
        if any(f"/{s}" in path for s in FFN):
            assert not any(f"/{s}" in path for s in ATTENTION), p
    if program == "serving_tick":       # one wrapper a layer, and no other
        layered = [p for p in paths if "/tick/layer/" in scope_path(p)]
        assert {bucket_of(p) for p in layered} == want - {"embed_head"}


def test_the_delta_rule_ticks_kernels_lie_where_their_bucket_reads(
        devices, monkeypatch):
    """A pool of whole blocks of slots (16: what ``ops/conv_step.py``
    walks) puts the window kernel on the tick's path beside the state
    kernel (ISSUE 41): every equation still lies in one bucket, both
    kernels under ``attn_core`` — ``conv_step`` in ``block/kda/conv``,
    ``kda_step`` in ``block/kda/state_update`` — and the tick's busy list,
    built where the first delta-rule layer wants it, in ``block/kda/conv``
    (the latent layers' row writer is handed the same list)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    tick = _served_programs("kda+mla+moe", devices, n_slots=16)[
        "serving_tick"]
    paths = list(paths_of(tick.jaxpr))
    assert not [p for p in paths if bucket_of(p) is None]
    assert {bucket_of(p) for p in paths} == SERVED | EXPERTS
    where = {k: {leaf_of(p) for p in paths
                 if p.endswith("/pallas_call") and f"/{k}/" in p}
             for k in ("conv_step", "kda_step", "cache_write_rows")}
    assert where == {
        "conv_step": {("attn_core", "block/kda/conv")},
        "kda_step": {("attn_core", "block/kda/state_update")},
        "cache_write_rows": {("cache_write", "cache_write")}}
    sorts = {leaf_of(p) for p in paths if p.endswith("/sort")
             and "/block/moe/" not in scope_path(p)}
    assert sorts == {("attn_core", "block/kda/conv")}


UNIT = [
    ("jit(serving_tick)/jit(main)/jit(shmap_body)/tick/embed/select_n",
     "embed_head", "tick/embed"),
    ("jit(serving_tick)/tick/head/cond/branch_1_fun/argmax:",
     "embed_head", "tick/head"),
    ("jit(serving_tick)/tick/layer/block/attn/core/cache_write/scatter",
     "cache_write", "cache_write"),
    ("jit(serving_tick)/tick/layer/block/attn/core/block/attn/window/"
     "decode_attn_gqa", "attn_core", "block/attn/core"),
    ("jit(serving_tick)/tick/layer/block/mla/core/tick/work_list/cumsum",
     "attn_core", "tick/work_list"),
    ("jit(serving_tick)/tick/layer/block/attn/gate/logistic",
     "attn_proj", "block/attn/gate"),
    ("jit(serving_tick)/tick/layer/block/kda/gate/logistic",
     "attn_core", "block/kda/gate"),
    ("jit(serving_tick)/tick/layer/block/kda/proj/dot_general",
     "attn_proj", "block/kda/proj"),
    ("jit(serving_tick)/tick/layer/block/mlp/block/moe/dispatch/while/body/"
     "add", "moe_route", "block/moe/dispatch"),
    ("jit(serving_tick)/tick/layer/block/mlp/block/moe/gmm/moe_gmm",
     "moe_experts", "block/moe/gmm"),
    ("jit(serving_tick)/tick/layer/block/mlp/block/moe/shared/dot_general",
     "ffn_dense", "block/moe/shared"),
    ("jit(serving_tick)/tick/layer/block/mlp/dot_general:",
     "ffn_dense", "block/mlp"),
    ("jit(serving_prefill_1024)/prefill/head/dot_general",
     "embed_head", "prefill/head"),
    # a wrapper alone names no bucket, nor does a path without scopes
    ("jit(serving_tick)/tick/layer/add", None, None),
    ("jit(serving_tick)/tick/attn/block/mla/dot_general", None, None),
    ("jit(serving_tick)/jit(main)/convert_element_type", None, None),
    ("", None, None),
    # whole components only: ``embed`` is not ``tick/embed``'s bucket
    ("jit(f)/attick/embedding/add", None, None),
    # the train step, by phase; the leaf is the block's own scope
    ("jit(train_step)/jit(main)/optimizer/mul", "optimizer", "optimizer"),
    ("jit(train_step)/loss_grad/jvp(block/attn)/dot_general",
     "fwd", "block/attn"),
    ("jit(train_step)/loss_grad/transpose(jvp(block/attn))/dot_general",
     "bwd", "block/attn"),
    ("jit(train_step)/loss_grad/transpose(jvp(block/mlp))/"
     "rematted_computation/jvp(block/mlp)/tanh", "bwd", "block/mlp"),
    ("jit(train_step)/loss_grad/jvp(head_ce)/fused_ce_stats",
     "fwd", "head_ce"),
    # the loss's one backward kernel, as its custom_vjp's rule is traced
    ("jit(train_step)/loss_grad/transpose(loss_grad)/jvp(head_ce)/"
     "fused_ce_grads/pallas_call", "bwd", "head_ce"),
    ("jit(train_step)/loss_grad/transpose(jvp(embed))/scatter-add",
     "bwd", "embed"),
    ("jit(train_step)/loss_grad/pmean", "fwd", "loss_grad"),
    # under ``loss_grad`` the phase wins over a served program's scope
    ("jit(train_step)/loss_grad/transpose(jvp(block/kda))/"
     "transpose(jvp(gate))/mul", "bwd", "loss_grad"),
]


@pytest.mark.parametrize("op_name,bucket,leaf", UNIT,
                         ids=[str(i) for i in range(len(UNIT))])
def test_bucket_of_and_the_phase_rule(op_name, bucket, leaf):
    assert bucket_of(op_name) == bucket
    assert leaf_of(op_name) == (bucket, leaf)


def test_the_table_is_ordered_inner_scopes_first():
    """A scope that holds another comes after it, and every bucket the
    manifest's ``tick_ms.*`` / ``step_ms.*`` metrics read is in the table."""
    scopes = [s for s, _ in BUCKETS]
    assert len(set(scopes)) == len(scopes)
    assert scopes.index("cache_write") < scopes.index("block/attn/core")
    assert scopes.index("tick/work_list") < scopes.index("block/mla/core")
    assert scopes.index("block/moe/route") < scopes.index("block/mlp")
    assert scopes.index("loss_grad") < scopes.index("block/kda/gate")
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        names = {m["name"] for m in json.load(f)["per_layer"]}
    buckets = {b for _, b in BUCKETS if b is not PHASE} | set(PHASE)
    for bucket in buckets:
        prefix = "step_ms." if bucket in PHASE + ("optimizer",) \
            else "tick_ms."
        assert prefix + bucket in names
    assert {"tick_ms.unscoped", "step_ms.unscoped"} <= names


# --------------------------------------------------------------------------
# a trained model with windows, rotations and routed experts (ISSUE 38):
# every equation of its train step lies under ONE leaf of the step's own
# vocabulary, which ``benchmark/harness/train_scope_trace.py`` books by
# --------------------------------------------------------------------------

#: the leaves of a windowed-GQA + routed-experts train step inside
#: ``loss_grad``; the FFN half's own scope comes last (its experts' nest in it)
TRAIN_LEAVES = ("embed", "head_ce", "block/attn/proj", "block/attn/core",
                "block/moe/route", "block/moe/dispatch", "block/moe/gmm",
                "block/mlp")


def _mellum_train_step(devices, remat):
    import optax

    from chainermn_tpu.parallel import (make_hybrid_shard_map_step,
                                        tp_transformer_lm_loss)
    from chainermn_tpu.parallel.blocks import lm_specs

    mod = _fixture("test_mellum2_training")
    mesh = mn.make_nd_mesh(("data", "model"), (1, 1), devices[:1])
    params = mod.ref.init_params(jax.random.PRNGKey(0), mod.CFG)
    optimizer = optax.adamw(1e-3)
    step = make_hybrid_shard_map_step(
        partial(tp_transformer_lm_loss, head_dim=mod.HEAD_DIM,
                axis_name="model", attn_impl="flash", ce_impl="fused",
                arch=mod.ARCH, remat=remat, aux=True),
        optimizer, mesh, params, lm_specs(mod.ARCH, params, "model"),
        data_axis="data", batch_spec=P("data"), has_aux=True, donate=False,
        aux_specs={"counts": P(), "routes": P("data")})
    # 128 positions: the flash backward takes its kernels from a lane
    # multiple on (below it the XLA scan, which the chip never runs here)
    return jax.make_jaxpr(step)(params, optimizer.init(params),
                                (np.zeros((2, 128 + 1), np.int32),))


@pytest.mark.parametrize("remat", [False, True], ids=["saved", "recomputed"])
def test_every_equation_of_the_expert_train_step_lies_under_one_leaf(
        devices, monkeypatch, remat):
    from benchmark.harness import train_scope_trace

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    paths = list(paths_of(_mellum_train_step(devices, remat).jaxpr))
    assert len(paths) > 500
    assert {bucket_of(p) for p in paths} == {"fwd", "bwd", "optimizer"}
    seen = set()
    for p in paths:
        path = scope_path(p)
        if "/optimizer/" in path:
            assert "/loss_grad/" not in path, p
            continue
        assert "/loss_grad/" in path, p
        leaves = [leaf for leaf in TRAIN_LEAVES if f"/{leaf}/" in path]
        if not leaves:
            # the step builder's own: the batch cut into inputs and
            # targets, the loss's mean over the data axis and the aux's sum
            # (no equation of the model)
            assert p.rsplit("/", 1)[-1] in (
                "slice", "div", "pvary", "psum_invariant"), p
            continue
        # one leaf: an expert scope only inside the FFN half, and nothing
        # of one half under the other's scopes
        inner = [leaf for leaf in leaves if leaf != "block/mlp"]
        assert len(inner) <= 1, p
        # (the layers' counts are summed under the route's scope at the
        # loss's end, outside any block)
        if inner and inner[0] in ("block/moe/dispatch", "block/moe/gmm"):
            assert "block/mlp" in leaves, p
        if "block/mlp" in leaves:
            assert "/block/attn/" not in path, p
        seen.add((inner or leaves)[0])
        # the banded kernels lie under the window's wrapper, inside core
        if "window_flash" in p:
            assert "/block/attn/core/" in path \
                and "/block/attn/window/" in path, p
    assert seen == set(TRAIN_LEAVES)
    # the same table's served rows, the phase's scope taken off the path
    booked = {train_scope_trace.bucket_of(p) for p in paths}
    assert booked == {None, "optimizer", "attn_proj", "attn_core",
                      "moe_route", "moe_experts", "ffn_dense"}
    assert set(train_scope_trace.TRAIN_BUCKETS) <= booked
    # each kernel of the step sits where its metric looks for it (a
    # kernel's own name is the last scope of its call, inside the autodiff
    # wrapping where no ``jit`` stands between: ``jvp(moe_gmm_glu)``)
    kernel_of = lambda p: scope_path(p).rstrip("/").rsplit("/", 2)[-2]
    where = {k: {train_scope_trace.bucket_of(p) for p in paths
                 if p.endswith("/pallas_call") and kernel_of(p) == k}
             for k in ("flash_fwd", "flash_bwd", "window_flash_fwd",
                       "window_flash_bwd", "moe_gmm", "moe_gmm_dw",
                       "moe_gmm_glu", "moe_gmm_glu_dx")}
    assert where == {"flash_fwd": {"attn_core"}, "flash_bwd": {"attn_core"},
                     "window_flash_fwd": {"attn_core"},
                     "window_flash_bwd": {"attn_core"},
                     "moe_gmm": {"moe_experts"},
                     "moe_gmm_dw": {"moe_experts"},
                     "moe_gmm_glu": {"moe_experts"},
                     "moe_gmm_glu_dx": {"moe_experts"}}
    # ... and the fused pair under the products' own scope, in the phase
    # that runs it: the transposed kernel in the backward alone
    for p in paths:
        if p.endswith("/pallas_call") and "moe_gmm_glu" in kernel_of(p):
            assert "/block/moe/gmm/" in scope_path(p), p
            if kernel_of(p) == "moe_gmm_glu_dx":
                assert bucket_of(p) == "bwd", p


# --------------------------------------------------------------------------
# a served model with selective state-space layers (ISSUE 40): its programs
# lie under the same vocabulary plus ``block/mamba/{proj,conv,core}``, which
# ``benchmark/harness/ssm_scope_trace.py`` books by the accepted table with
# three rows laid before it
# --------------------------------------------------------------------------

@pytest.mark.parametrize("program", ["serving_tick", "serving_prefill"])
def test_every_equation_of_a_selective_state_model_lies_in_one_bucket(
        devices, monkeypatch, program):
    from benchmark.harness import ssm_scope_trace

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    FIXTURES["mamba+mqa"] = "test_jamba_serving"
    try:
        jaxpr = _served_programs("mamba+mqa", devices)[program]
    finally:
        del FIXTURES["mamba+mqa"]
    paths = list(paths_of(jaxpr.jaxpr))
    assert len(paths) > 100
    unscoped = [p for p in paths if ssm_scope_trace.bucket_of(p) is None]
    assert not unscoped, unscoped[:10]
    found = {ssm_scope_trace.bucket_of(p) for p in paths}
    assert found == SERVED | {"ssm_proj", "ssm_core"}
    scopes = {ssm_scope_trace.leaf_of(p)[1] for p in paths}
    assert {"block/mamba/proj", "block/mamba/conv", "block/mamba/core",
            "block/attn/proj", "block/attn/core", "block/mlp"} <= scopes
    # the accepted table alone books the Mamba scopes to nothing — why the
    # cell is not on ``tick_ms.unscoped``'s list — and the rest as ever
    for p in paths:
        mine, theirs = ssm_scope_trace.bucket_of(p), bucket_of(p)
        assert theirs == (None if mine in ("ssm_proj", "ssm_core")
                          else mine), p
        path = scope_path(p)
        if any(f"/{s}" in path for s in FFN):
            assert "/block/mamba/" not in path, p
    kernel = "ssm_step" if program == "serving_tick" else "selective_scan"
    assert any(p.endswith("/pallas_call") and f"/block/mamba/core/" in
               scope_path(p) and kernel in p for p in paths)
