"""Ahead-of-time compiles for the chip, without the chip.

The TPU's compiler is installed in the sandbox and compiles for a chip
that is DESCRIBED (``v5e:2x2``, device_kind "TPU v5 lite"), not attached.
These tests hand it the main path's kernels at the real widths
``chip_smoke.py`` runs — and the whole 135M LM train step and the serving
programs inside ``shard_map`` with vma checking on — so a kernel the
chip's compiler would refuse (unaligned slice, too much VMEM, a program
that does not fit 16 GB) fails here, at no chip time.  A compile that
passes is not a chip run and says nothing about results or speed.

Rules this file keeps (on-chip-measurement guide §2): the topology is
described INSIDE a module-scoped fixture that skips when it cannot be —
never at import, in a ``skipif``/``parametrize`` argument or in
``conftest.py`` (only one process may load the TPU library, and xdist
workers that collect different tests run none); every compile happens in
this process; ALL such tests live in this one file; the persistent
compile cache is off around them (such an entry cannot be read back
without a chip).  Code that asks ``jax.default_backend()`` sees the CPU
here, so the TEST steers it (explicit ``interpret=False`` / impl names,
or a monkeypatched ``default_backend``) — never a new program option.
"""

import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

# the real widths (chip_smoke.SIZES' 135M LM row)
VOCAB, D_MODEL, N_LAYERS, N_HEADS, HEAD_DIM, SEQ, BATCH = (
    32768, 1024, 8, 8, 128, 1024, 8)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler / lock held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_tpu(monkeypatch):
    """Steer ``jax.default_backend()`` branches to their TPU side."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _n_kernels(fn, *args, names=()) -> int:
    """Compile ``fn`` for the described chip; count its Pallas kernels.
    ``names``: each kernel's own ``name=``, which the custom call's
    instruction name must hold (alone, ``%flash_fwd.2``, or inside the
    autodiff wrapping when no scope stands between, ``%jvp_flash_fwd_``):
    the profiler's ops line calls the kernel by that instruction name."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    calls = [ln.split(" = ")[0] for ln in text.split("\n")
             if "tpu_custom_call" in ln]
    for name in names:
        assert any(name in c for c in calls), (name, calls)
    return text.count("tpu_custom_call")


#: the grouped expert products' kernels, the longest name first: a call is
#: the first whose name its instruction's holds
MOE_KERNELS = ("moe_gmm_glu_dx", "moe_gmm_glu", "moe_gmm_dw", "moe_gmm_rows",
               "moe_gmm_sum", "moe_gmm")


#: the fused loss's kernels; the bare stem last catches any other
FUSED_CE_KERNELS = ("fused_ce_stats", "fused_ce_grads", "fused_ce")
#: the forward's statistics and ONE backward kernel: each logits tile is
#: formed once for both gradients (ISSUE 48)
FUSED_CE_CALLS = {"fused_ce_stats": 1, "fused_ce_grads": 1}


def _kernel_calls(text: str, kernels) -> dict:
    """Custom calls of a compiled program by kernel: each counts for the
    first of ``kernels`` whose name its instruction's holds."""
    calls = {}
    for ln in text.split("\n"):
        if "tpu_custom_call" not in ln:
            continue
        name = ln.split(" = ")[0]
        kernel = next((k for k in kernels if k in name), None)
        if kernel:
            calls[kernel] = calls.get(kernel, 0) + 1
    return calls


def _moe_kernel_calls(text: str) -> dict:
    """Custom calls of a compiled program by grouped-product kernel."""
    return _kernel_calls(text, MOE_KERNELS)


def test_flash_attention_fwd(one_chip):
    from chainermn_tpu.ops.flash_attention import flash_attention

    q = _sds((4, 2048, 8, 128), jnp.bfloat16, one_chip)
    n = _n_kernels(partial(flash_attention, causal=True, interpret=False),
                   q, q, q, names=("flash_fwd",))
    assert n >= 1


def test_flash_attention_fwd_bwd(one_chip):
    from chainermn_tpu.ops.flash_attention import flash_attention

    q = _sds((4, 2048, 8, 128), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, interpret=False)
        return out.astype(jnp.float32).sum()

    n = _n_kernels(jax.grad(loss, argnums=(0, 1, 2)), q, q, q,
                   names=("flash_fwd", "flash_bwd"))
    assert n >= 2       # forward + backward kernels


# (batch, seq, heads, head width, backward too): the benchmark's cells at
# their own shapes — a grid cell there is wider than a sub-block, so the
# kernels' sub-block loops (traced trip counts, sublane slices of Q/K/V,
# lane slices of the LSE row) meet the chip's compiler here
FLASH_CELL_SHAPES = {
    "gpt2-medium-train-s1024": (8, 1024, 16, 64, True),
    "deepseek-v3-prefill-1024": (1, 1024, 128, 192, False),
    "deepseek-v3-prefill-3072": (1, 3072, 128, 192, False),
}


@pytest.mark.parametrize("cell", sorted(FLASH_CELL_SHAPES))
def test_flash_attention_at_the_cells_shapes(one_chip, cell):
    from chainermn_tpu.ops.flash_attention import flash_attention

    b, s, h, d, bwd = FLASH_CELL_SHAPES[cell]
    q = _sds((b, s, h, d), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, interpret=False)
        return out.astype(jnp.float32).sum()

    if bwd:
        n = _n_kernels(jax.grad(loss, argnums=(0, 1, 2)), q, q, q,
                       names=("flash_fwd", "flash_bwd"))
        assert n >= 2
    else:
        n = _n_kernels(partial(flash_attention, causal=True,
                               interpret=False), q, q, q,
                       names=("flash_fwd",))
        assert n >= 1


# (rows, d, table rows): chip_smoke's 135M LM; the two training cells —
# GPT-2's 8192 float32 ``dh`` rows stay whole in VMEM (32 MiB), Mellum's
# 16384 x 2304 are cut into super-blocks and ``dtable`` is carried in HBM:
# the chip compiler's verdict on each kernel's scoped VMEM (ISSUE 48)
FUSED_CE_SHAPES = {
    "lm-135m": (8192, D_MODEL, VOCAB, False),
    "gpt2-medium-train-s1024": (8192, 1024, 50304, False),
    "mellum2-ep4-train-s8192": (16384, 2304, 24576, True),
}


@pytest.mark.parametrize("cell", sorted(FUSED_CE_SHAPES))
def test_fused_cross_entropy_fwd_bwd(one_chip, cell):
    from chainermn_tpu.ops.fused_ce import fused_cross_entropy

    t, d, v, carried = FUSED_CE_SHAPES[cell]
    h = _sds((t, d), jnp.bfloat16, one_chip)
    table = _sds((v, d), jnp.bfloat16, one_chip)
    tgt = _sds((t,), jnp.int32, one_chip)

    def loss(h, table, tgt):
        return fused_cross_entropy(h, table, tgt, interpret=False).mean()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        h, table, tgt).compile()
    text = compiled.as_text()
    assert _kernel_calls(text, FUSED_CE_KERNELS) == FUSED_CE_CALLS
    # no (T, V) array; the carried float32 ``dtable`` is the one temporary
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= (v * d * 4 + (1 << 20) if carried else 1 << 20), temp
    # ... handed in as zeros and written in place
    assert ("output_to_operand_aliasing={{1}: (5" in text) == carried


@pytest.mark.parametrize("face", ["scalar", "vector", "busy"])
def test_decode_attend(one_chip, face):
    """One position for the batch (``lm_generate``), one per cache row, or
    one per cache row and the rows that are busy (the serving tick): the
    same kernel on a grid whose bound is the work list's length."""
    from chainermn_tpu.ops.decode_attention import decode_attend

    b, s, h, hd = 8, 2048, 16, 128
    q = _sds((b, h * hd), jnp.bfloat16, one_chip)
    kc = _sds((b, s, h * hd), jnp.bfloat16, one_chip)
    pos = _sds(() if face == "scalar" else (b,), jnp.int32, one_chip)
    busy = (_sds((b,), jnp.bool_, one_chip),) if face == "busy" else ()
    n = _n_kernels(partial(decode_attend, n_heads=h, head_dim=hd,
                           interpret=False), q, kc, kc, pos, *busy,
                   names=("decode_attn_mha",))
    assert n >= 1


def test_cache_append(one_chip, as_tpu):
    # ops/kv_cache.py refuses impl="pallas", interpret=False off-TPU, so
    # this compile needs the default_backend steer
    from chainermn_tpu.ops.kv_cache import cache_append

    b, s, d = 8, 2048, 16 * 128
    kc = _sds((b, s, d), jnp.bfloat16, one_chip)
    new = _sds((b, 1, d), jnp.bfloat16, one_chip)
    pos = _sds((), jnp.int32, one_chip)
    n = _n_kernels(partial(cache_append, axis=1, impl="pallas",
                           interpret=False), kc, kc, new, new, pos,
                   names=("kv_cache_write",))
    assert n >= 1


@pytest.mark.parametrize("shape,n_bufs", [
    ((32, 1024, 1024), 2),      # GPT-2 medium's K and V rows
    ((24, 512, 1024), 2),       # Laguna's sliding layers' ring
    ((64, 4096, 640), 1),       # DeepSeek's padded latent rows
], ids=["gpt2_rows", "laguna_ring", "deepseek_latent"])
def test_cache_write_rows(one_chip, as_tpu, shape, n_bufs):
    """The served tick's writer (per-slot positions, the busy slots alone):
    one kernel a layer, every buffer aliased to its result — the compiled
    program holds no copy of a buffer and no loop."""
    from chainermn_tpu.ops.kv_cache import write_rows

    b, _, d = shape
    bufs = (_sds(shape, jnp.bfloat16, one_chip),) * n_bufs
    new = (_sds((b, 1, d), jnp.bfloat16, one_chip),) * n_bufs
    pos = _sds((b,), jnp.int32, one_chip)
    busy = _sds((b,), jnp.bool_, one_chip)
    fn = jax.jit(lambda bufs, new, pos, busy: write_rows(bufs, new, pos, busy),
                 donate_argnums=(0,))
    text = fn.lower(bufs, new, pos, busy).compile().as_text()
    calls = [ln.split(" = ")[0] for ln in text.split("\n")
             if "custom_call_target=\"tpu_custom_call\"" in ln]
    assert len(calls) == 1 and "cache_write_rows" in calls[0], calls
    assert " while(" not in text
    assert not [ln for ln in text.split("\n") if " copy(" in ln
                and f"[{shape[0]},{shape[1]},{shape[2]}]" in ln]


@pytest.mark.parametrize("shape", [(64, 3, 12288), (128, 3, 5120)],
                         ids=["kimi_window", "jamba_window"])
def test_conv_step(one_chip, shape):
    """The delta-rule tick's window step at the published widths (Kimi's
    fused ``q | k | v``; Jamba's inner channels, which do not take it yet:
    ROADMAP S1(c)).  The pool's window reaches the kernel as the ``(W-1,
    N, C)`` array the chip's default layout makes of it — a bitcast, not a
    copy — is aliased to its result, and the program holds no loop over
    the slots."""
    from chainermn_tpu.ops.conv_step import conv_step

    n, keep, c = shape
    fn = jax.jit(lambda win, new, wt, busy: conv_step(win, new, wt, busy),
                 donate_argnums=(0,))
    text = fn.lower(_sds(shape, jnp.bfloat16, one_chip),
                    _sds((n, 1, c), jnp.bfloat16, one_chip),
                    _sds((keep + 1, c), jnp.bfloat16, one_chip),
                    _sds((n,), jnp.bool_, one_chip)).compile().as_text()
    calls = [ln for ln in text.split("\n")
             if "custom_call_target=\"tpu_custom_call\"" in ln]
    assert len(calls) == 1 and "%conv_step" in calls[0].split(" = ")[0]
    assert f"bf16[{keep},{n},{c}]" in calls[0].split(" = ")[1]
    assert " while(" not in text and " sort(" not in text
    assert not [ln for ln in text.split("\n") if " copy(" in ln and (
        f"bf16[{n},{keep},{c}]" in ln or f"bf16[{keep},{n},{c}]" in ln)]


def test_conv3x3_backward(one_chip):
    from chainermn_tpu.ops.conv_backward import conv3x3_dgrad, conv3x3_wgrad

    x = _sds((32, 56, 56, 64), jnp.bfloat16, one_chip)
    w = _sds((3, 3, 64, 64), jnp.bfloat16, one_chip)

    def bwd(x, dy, w):
        return (conv3x3_dgrad(dy, w, x.shape, 1, interpret=False),
                conv3x3_wgrad(x, dy, 1, interpret=False))

    n = _n_kernels(bwd, x, x, w, names=("conv_bwd_dx", "conv_bwd_dw"))
    assert n >= 2


def _lm_shapes(mesh, max_len, n_heads=N_HEADS):
    from chainermn_tpu.parallel import (init_tp_transformer_lm,
                                        transformer_lm_specs)

    params = jax.eval_shape(lambda: init_tp_transformer_lm(
        jax.random.PRNGKey(0), VOCAB, D_MODEL, n_heads, N_LAYERS,
        max_len=max_len, dtype=jnp.bfloat16))
    specs = transformer_lm_specs(params, "model")

    def shaped(tree, tree_specs):
        return jax.tree_util.tree_map(
            lambda x, s: _sds(x.shape, x.dtype, NamedSharding(mesh, s)),
            tree, tree_specs)

    return params, specs, shaped


def test_lm_135m_train_step_in_shard_map(topo, as_tpu):
    """The whole LM train step chip_smoke's train-lm phase runs:
    make_hybrid_shard_map_step + flash attention + fused CE on a (1, 1)
    mesh, vma checking on."""
    import optax

    from chainermn_tpu.parallel import (make_hybrid_shard_map_step,
                                        state_specs_like,
                                        tp_transformer_lm_loss)

    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    params, specs, shaped = _lm_shapes(mesh, SEQ)
    loss_fn = partial(tp_transformer_lm_loss, head_dim=HEAD_DIM,
                      axis_name="model", attn_impl="flash", ce_impl="fused")
    optimizer = optax.sgd(1e-2)
    step = make_hybrid_shard_map_step(loss_fn, optimizer, mesh, params,
                                      specs, data_axis="data",
                                      batch_spec=P("data"))
    opt_state = jax.eval_shape(optimizer.init, params)
    batch = (_sds((BATCH, SEQ + 1), jnp.int32,
                  NamedSharding(mesh, P("data"))),)
    compiled = step.lower(
        shaped(params, specs),
        shaped(opt_state, state_specs_like(optimizer, params, specs)),
        batch).compile()
    text = compiled.as_text()
    # flash fwd + bwd per layer, plus the fused-CE kernels
    assert text.count("tpu_custom_call") >= 2 * N_LAYERS + 2
    # every kernel and the program carry their own names (the profiler's
    # ops and modules lines are read by them: benchmark/layer_metrics)
    assert "HloModule jit_train_step" in text
    for kernel in ("flash_fwd", "flash_bwd", "fused_ce_stats",
                   "fused_ce_grads"):
        assert f"%{kernel}" in text, kernel
    assert _kernel_calls(text, FUSED_CE_KERNELS) == FUSED_CE_CALLS
    # the step's scopes, by which ``step_ms.*`` split it: the phases and,
    # inside ``loss_grad``, the blocks' (autodiff wraps each: ``jvp(...)``)
    _assert_scopes(text, "/loss_grad/", "/optimizer/", "jvp(embed)",
                   "transpose(jvp(block/attn))", "transpose(jvp(block/mlp))",
                   "jvp(head_ce)")
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 16 * 2 ** 30)      # fits one v5e chip's HBM


def test_windowed_expert_train_step_in_shard_map(topo, as_tpu):
    """The train step of a model with sliding-window and full GQA layers and
    softmax-routed experts (``mellum2-12b-ep4``'s published widths, one
    sliding and one full layer, one 8192-token sequence) for the described
    v5e: the banded and the causal flash kernels forward and backward (GQA
    group 8), the grouped expert product with its weight-gradient kernel,
    the fused loss at d 2304 (a scoped-VMEM limit of its own), per-layer
    recomputation, the routing counts out as aux (ISSUE 38)."""
    import json

    import optax

    from chainermn_tpu.parallel import (make_hybrid_shard_map_step,
                                        state_specs_like,
                                        tp_transformer_lm_loss)
    from chainermn_tpu.parallel.blocks import (LMArch, MoEConfig, Rotary,
                                               lm_specs)

    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(os.path.dirname(here), "benchmark", "configs",
                           "mellum2-12b-ep4.json")) as f:
        cfg = json.load(f)
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    held, inner = cfg["num_experts_held"], cfg["moe_intermediate_size"]
    vocab, seq = cfg["vocab_size"], 8192
    full = cfg["rope_parameters"]["full_attention"]
    arch = LMArch(
        norm="rmsnorm", norm_eps=cfg["rms_norm_eps"], mlp="swiglu",
        attn="mha", tied_head=False, embed_scale=False, attn_bias=False,
        layer_kinds=("moe", "moe"), windows=(cfg["sliding_window"], None),
        rotary=(Rotary(theta=5e5), Rotary(
            theta=5e5, yarn=(full["factor"],
                             full["original_max_position_embeddings"],
                             full["beta_fast"], full["beta_slow"]),
            attention_factor=full["attention_factor"])),
        moe=MoEConfig(n_experts=cfg["num_experts"],
                      top_k=cfg["num_experts_per_tok"], n_group=1,
                      topk_group=1, routed_scaling_factor=1.0,
                      held=(0, held), router="softmax", n_shared=0))
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    block = {"ln1_scale": f32(d), "ln2_scale": f32(d),
             "attn": {"wq": f32(d, heads * hd), "wkv": f32(d, 2 * kv * hd),
                      "wo": f32(heads * hd, d)},
             "moe": {"router": f32(d, cfg["num_experts"]),
                     "w_gate": f32(held, d, inner),
                     "w_up": f32(held, d, inner),
                     "w_down": f32(held, inner, d)}}
    params = {"embed": f32(vocab, d), "head": f32(vocab, d),
              "lnf_scale": f32(d), "blocks": [block, block]}
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    specs = lm_specs(arch, params, "model")
    lm_loss = partial(tp_transformer_lm_loss, head_dim=hd, axis_name="model",
                      attn_impl="flash", ce_impl="fused", arch=arch,
                      remat=True, aux=True)
    optimizer = optax.adamw(3e-4)
    step = make_hybrid_shard_map_step(
        lambda p, batch: lm_loss(jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16), p), batch),
        optimizer, mesh, params, specs, data_axis="data",
        batch_spec=P("data"), has_aux=True,
        aux_specs={"counts": P(), "routes": P("data")})
    shaped = lambda tree, sp: jax.tree_util.tree_map(
        lambda a, spec: _sds(a.shape, a.dtype, NamedSharding(mesh, spec)),
        tree, sp)
    opt_state = jax.eval_shape(optimizer.init, params)
    compiled = step.lower(
        shaped(params, specs),
        shaped(opt_state, state_specs_like(optimizer, params, specs)),
        (_sds((1, seq + 1), jnp.int32, NamedSharding(mesh, P("data"))),)
    ).compile()
    text = compiled.as_text()
    assert "HloModule jit_train_step" in text
    # every kernel by its own name: the profiler's ops line is read by them
    for kernel in ("window_flash_fwd", "window_flash_bwd", "flash_fwd",
                   "flash_bwd", "moe_gmm", "moe_gmm_dw", "moe_gmm_glu",
                   "moe_gmm_glu_dx", "fused_ce_stats", "fused_ce_grads"):
        assert f"%{kernel}" in text, kernel
    assert _kernel_calls(text, FUSED_CE_KERNELS) == FUSED_CE_CALLS
    # an expert layer's nine grouped-product calls (ISSUE 47; the cell's four
    # layers: 8 / 4 / 12 / 12, 36 where 48): the fused gate/up product
    # forward and recomputed, its one transposed kernel, the down product
    # forward, recomputed and transposed, three weight gradients
    assert _moe_kernel_calls(text) == {
        "moe_gmm_glu": 2 * 2, "moe_gmm_glu_dx": 2, "moe_gmm": 2 * 3,
        "moe_gmm_dw": 2 * 3}
    # the weight gradient is written into the zero buffer it is handed
    assert "input_output_alias" in text
    # the step's leaves, by which ``train_ms.*`` split it
    _assert_scopes(text, "/loss_grad/", "/optimizer/", "block/attn/proj",
                   "block/attn/core", "block/attn/window", "block/moe/route",
                   "block/moe/dispatch", "block/moe/gmm", "head_ce")
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 15 * 2 ** 30)


def test_expert_layer_backward_walks_the_live_chunks(one_chip, monkeypatch):
    """The trained expert layer alone — recomputed forward and backward at
    ``mellum2-ep4-train-s8192``'s sizes (2 x 8192 tokens, 8 of 64 experts a
    token, 16 held, d 2304) — for the described v5e: the backward's
    row-side pass is ONE ``while`` loop over the live chunks, which updates
    the buffers it carries in place — alone it asks for less than ONE
    chunk's float32 rows of temporaries, where the parent's whole-buffer
    formulation (``tests/moe_rows_parent.py``) asks for all ``M`` of them
    (ISSUE 39)."""
    import json
    import sys

    from chainermn_tpu.parallel import moe as moe_mod

    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, here)
    import moe_rows_parent

    with open(os.path.join(os.path.dirname(here), "benchmark", "configs",
                           "mellum2-12b-ep4.json")) as f:
        cfg = json.load(f)
    t, k, d = 2 * 8192, cfg["num_experts_per_tok"], cfg["hidden_size"]
    held, inner = cfg["num_experts_held"], cfg["moe_intermediate_size"]
    tm = moe_mod._row_tile(t * k)
    chunk = moe_mod._row_chunk(t * k, tm)
    m = -(-(t * k + held * (tm - 1)) // tm) * tm
    assert (tm, chunk, m) == (512, 8192, 139264)
    sds = lambda dtype, *shape: _sds(shape, dtype, one_chip)
    bf16 = partial(sds, jnp.bfloat16)
    args = (bf16(t, d),
            {"w_gate": bf16(held, d, inner), "w_up": bf16(held, d, inner),
             "w_down": bf16(held, inner, d)},
            sds(jnp.int32, t, k), sds(jnp.float32, t, k))

    def compiled():
        # (a fresh function a compile: tracing is cached by the function)
        def layer(x, p, idx, gates):
            return moe_mod._held_experts_product(
                x, p, idx, gates, 0, held, True, False)[0].astype(x.dtype)

        def grads(x, p, idx, gates):
            return jax.grad(lambda x, p, g: jax.checkpoint(layer)(
                x, p, idx, g).astype(jnp.float32).sum(),
                argnums=(0, 1, 2))(x, p, gates)

        return jax.jit(grads).lower(*args).compile()

    loops = lambda c: sum(" while(" in ln for ln in c.as_text().split("\n"))
    new = compiled()
    with monkeypatch.context() as mp:
        moe_rows_parent.install(mp)
        old = compiled()
    assert loops(new) - loops(old) == 1
    text = new.as_text()
    # (the layer alone: the compiler shares the forward with its
    # recomputation, which the step's checkpoint keeps apart)
    assert _moe_kernel_calls(text) == {
        "moe_gmm_glu": 1, "moe_gmm_glu_dx": 1, "moe_gmm": 2, "moe_gmm_dw": 3}
    assert "block/moe/gmm" in text
    # no sum of two rows' cotangents, and the transposed kernel writes both
    # products' cotangents over the products it read
    assert "add_any" not in text
    dx = next(ln for ln in text.split("\n")
              if ln.lstrip().startswith("%moe_gmm_glu_dx"))
    assert "output_to_operand_aliasing" in dx, dx[:400]

    # the pass alone, both ways: (rows, gates, dest, is_held, row_token,
    # n_live), dy -> (d_rows, d_gates)
    res = (bf16(m, d), sds(jnp.float32, t, k), sds(jnp.int32, t, k),
           sds(jnp.bool_, t, k), sds(jnp.int32, m), sds(jnp.int32))
    temp = lambda fn: jax.jit(fn).lower(res, sds(jnp.float32, t, d)) \
        .compile().memory_analysis().temp_size_in_bytes
    now = temp(lambda res, dy: moe_mod._combine_bwd(chunk, res, dy)[:2])
    was = temp(lambda res, dy: moe_rows_parent._combine_bwd(res[:5], dy)[:2])
    assert now <= chunk * d * 4 and was >= m * d * 4, (now, was)


def _assert_pool_written_in_place(text: str, pool_shape) -> None:
    """The program takes the cache pool donated: its result aliases the
    argument and no buffer of the pool's shape is copied to be written
    (un-donated, every K, V or latent buffer was: 48 a gpt2-medium tick)."""
    import re

    assert "input_output_alias" in text
    dims = ",".join(str(n) for n in pool_shape)
    copies = re.findall(rf"= bf16\[{dims}\]\S* copy\(", text)
    assert not copies, f"{len(copies)} pool-sized copies left"


def _assert_tick_writes_rows_in_place(text: str, layers: int) -> None:
    """The tick's new rows go through ONE ``cache_write_rows`` kernel a
    layer that keeps rows or a ring (ops/kv_cache.py::write_rows, under the
    layer's ``cache_write`` scope), and the per-slot ``while`` loops of the
    vmapped ``dynamic_update_slice`` are gone (ISSUE 37).  No reader of an
    accepted metric may match the kernel's name by substring."""
    calls = [ln.split(" = ")[0] for ln in text.split("\n")
             if "custom_call_target=\"tpu_custom_call\"" in ln]
    assert sum("cache_write_rows" in c for c in calls) == layers, calls
    assert "cache_write/jit(_write_rows_kernel)/cache_write_rows" in text
    assert not [ln for ln in text.split("\n")
                if " while(" in ln and "cache_write" in ln]
    assert not any(n in "cache_write_rows" for n in (
        "decode_attn", "moe_gmm", "kda", "mla", "flash", "fused_ce"))


def _assert_scopes(text: str, *scopes, tick: bool = False) -> None:
    """The compiled program's ``op_name`` metadata holds each scope path —
    what the chip's trace carries as ``tf_op`` and
    ``benchmark/harness/scope_trace.py::BUCKETS`` books by
    (docs/OBSERVABILITY.md has the vocabulary).  A tick wraps each whole
    layer in ``tick/layer`` — until PR 35 ``tick/attn``, a name that was
    wrong for the FFN half it also covered — with the token pick and the
    embedding under ``tick/embed`` and the final norm, the logits and the
    selection under ``tick/head``."""
    if tick:
        assert "tick/attn" not in text
        scopes += ("tick/embed", "tick/head")
    for scope in scopes:
        assert scope in text, scope


def _assert_resident_expert_layers(text: str, layers: int) -> None:
    """A TICK's expert layers take their rows and give their sum inside the
    grouped products (``parallel/moe.py::_rows_resident``, ISSUE 42): two
    kernels a layer, ``moe_gmm_rows`` and ``moe_gmm_sum``, whose names hold
    ``moe_gmm`` (what ``moe_gmm_ms_per_tick`` sums), and no staged
    ``moe_gmm`` call beside them."""
    assert _moe_kernel_calls(text) == {"moe_gmm_rows": layers,
                                       "moe_gmm_sum": layers}


# (heads, head_dim, slots, prompt, total): the LM width chip_smoke runs,
# and gpt2-medium's heads in the serving cell's pool (BENCHMARK.json)
SERVING_SHAPES = {"8x128": (N_HEADS, HEAD_DIM, 4, 512, 512 + 64),
                  "gpt2-medium": (16, 64, 32, 512, 1024)}


@pytest.mark.parametrize("shape", sorted(SERVING_SHAPES))
def test_serving_prefill_and_tick(topo, as_tpu, shape):
    """The ServingEngine's own prefill (prompt 512) and decode tick at
    the LM width.  DecodeEngine's constructor places params on devices,
    which a described chip cannot hold — so the program builders run on
    a bare instance given the same attributes."""
    from chainermn_tpu._compat import shard_map
    from chainermn_tpu.parallel import blocks
    from chainermn_tpu.serving.engine import DecodeEngine, result_size

    n_heads, head_dim, n_slots, prompt, total = SERVING_SHAPES[shape]
    mesh = Mesh(np.array(topo.devices[:1]), ("model",))
    params, specs, shaped = _lm_shapes(mesh, SEQ, n_heads)
    kv = P(None, None, "model")
    rep = NamedSharding(mesh, P())
    caches = [(_sds((n_slots, total, D_MODEL), jnp.bfloat16,
                    NamedSharding(mesh, kv)),) * 2 for _ in range(N_LAYERS)]
    eng = DecodeEngine.__new__(DecodeEngine)
    eng.mesh, eng.axis_name, eng.head_dim = mesh, "model", head_dim
    eng.arch, eng.n_counts = blocks.DEFAULT_ARCH, 0
    eng._specs, eng._shard_map, eng._P = specs, shard_map, P
    eng._cache_specs = [(kv, kv)] * N_LAYERS
    p = shaped(params, specs)

    prefill = eng._build_prefill(prompt).lower(
        p, caches, _sds((1, prompt), jnp.int32, rep),
        _sds((), jnp.int32, rep), _sds((), jnp.int32, rep),
        _sds((2,), jnp.uint32, rep), _sds((), jnp.float32, rep)).compile()
    # the prompt pass takes the flash kernel, one per layer
    assert prefill.as_text().count("tpu_custom_call") >= N_LAYERS
    assert f"HloModule jit_serving_prefill_{prompt}" in prefill.as_text()
    assert "%flash_fwd" in prefill.as_text()
    _assert_scopes(prefill.as_text(), "prefill/embed", "prefill/head",
                   "block/attn/proj", "block/attn/core", "cache_write",
                   "block/mlp")
    _assert_pool_written_in_place(prefill.as_text(),
                                  (n_slots, total, D_MODEL))

    tick = eng._build_tick().lower(
        p, caches, _sds((result_size(eng.arch, n_slots),), jnp.int32, rep),
        _sds((n_slots,), jnp.int32, rep), _sds((n_slots,), jnp.int32, rep),
        _sds((n_slots, 2), jnp.uint32, rep),
        _sds((n_slots,), jnp.float32, rep),
        _sds((n_slots,), jnp.bool_, rep)).compile()
    assert "HloModule jit_serving_tick" in tick.as_text()
    # the tick's per-slot positions and busy mask go to the flash-decode
    # kernel as one work list, one call a layer on a grid the list's
    # length bounds: a list or a bound Mosaic refused would fail here
    assert "%decode_attn_mha" in tick.as_text()
    assert tick.as_text().count("tpu_custom_call") >= N_LAYERS
    _assert_scopes(tick.as_text(), "tick/layer/block/attn/proj",
                   "tick/layer/block/attn/core/cache_write",
                   "block/attn/core/tick/work_list",
                   "block/attn/core/jit(decode_attend)/decode_attn_mha",
                   "tick/layer/block/mlp", tick=True)
    _assert_pool_written_in_place(tick.as_text(), (n_slots, total, D_MODEL))
    _assert_tick_writes_rows_in_place(tick.as_text(), N_LAYERS)


def test_latent_attention_and_expert_serving_programs(topo, as_tpu):
    """The serving programs of a latent-attention / routed-expert model at
    DeepSeek-V3's published widths (one dense and one expert layer, 16 of
    256 experts held, 64 slots of 4096 rows: the benchmark's
    ``deepseek-v3-ep16`` pool): the tick takes the absorbed flash-decode
    kernel over the 640-column latent pool and the grouped expert product,
    the prefill the flash forward kernel at 192/128 and the same product;
    kernels and programs keep the names the trace readers match."""
    from chainermn_tpu._compat import shard_map
    from chainermn_tpu.parallel import blocks
    from chainermn_tpu.parallel.blocks import LMArch, MLAConfig, MoEConfig
    from chainermn_tpu.serving.engine import DecodeEngine, result_size

    d, heads, q_rank, kv_rank, nope, rope, v = 7168, 128, 1536, 512, 128, 64, 128
    inner, e_inner, experts, held, vocab = 18432, 2048, 256, 16, 16160
    n_slots, total, prompt, layers = 64, 4096, 1024, 2
    arch = LMArch(
        norm="rmsnorm", norm_eps=1e-6, mlp="swiglu", attn="mla",
        tied_head=False, embed_scale=False, layer_kinds=("dense", "moe"),
        mla=MLAConfig(heads, q_rank, kv_rank, nope, rope, v, 10000.0,
                      (40, 4096, 32, 1, 1, 1)),
        moe=MoEConfig(experts, 8, 8, 4, 2.5, True, (0, held)))
    mesh = Mesh(np.array(topo.devices[:1]), ("model",))
    rep = NamedSharding(mesh, P())
    bf = jnp.bfloat16

    def gated(n_inner, lead=()):
        return {"w_gate": _sds(lead + (d, n_inner), bf, rep),
                "w_up": _sds(lead + (d, n_inner), bf, rep),
                "w_down": _sds(lead + (n_inner, d), bf, rep)}

    def block(kind):
        out = {"ln1_scale": _sds((d,), bf, rep),
               "ln2_scale": _sds((d,), bf, rep),
               "attn": {"wdq": _sds((d, q_rank), bf, rep),
                        "q_norm": _sds((q_rank,), bf, rep),
                        "wuq": _sds((q_rank, heads * (nope + rope)), bf, rep),
                        "wdkv": _sds((d, kv_rank + rope), bf, rep),
                        "kv_norm": _sds((kv_rank,), bf, rep),
                        "wukv": _sds((kv_rank, heads * (nope + v)), bf, rep),
                        "wo": _sds((heads * v, d), bf, rep)}}
        if kind == "dense":
            out["mlp"] = gated(inner)
        else:
            out["moe"] = dict(gated(e_inner, (held,)),
                              router=_sds((d, experts), bf, rep),
                              router_bias=_sds((experts,), jnp.float32, rep),
                              shared=gated(e_inner))
        return out

    table = NamedSharding(mesh, P("model", None))
    p = {"embed": _sds((vocab, d), bf, table),
         "head": _sds((vocab, d), bf, table),
         "lnf_scale": _sds((d,), bf, rep),
         "blocks": [block(k) for k in arch.layer_kinds]}
    layout = blocks.cache_layout(arch, layers, 0, "model")
    assert [[w for w, _ in bufs] for bufs in layout] == [[640]] * layers
    caches = [tuple(_sds((n_slots, total, w), bf, NamedSharding(mesh, spec))
                    for w, spec in bufs) for bufs in layout]
    eng = DecodeEngine.__new__(DecodeEngine)
    eng.mesh, eng.axis_name, eng.head_dim, eng.arch = mesh, "model", v, arch
    eng.n_counts = blocks.n_count_entries(arch)
    eng._specs = blocks.lm_specs(arch, p, "model")
    eng._shard_map, eng._P = shard_map, P
    eng._cache_specs = [tuple(spec for _, spec in bufs) for bufs in layout]

    tick = eng._build_tick().lower(
        p, caches, _sds((result_size(eng.arch, n_slots),), jnp.int32, rep),
        _sds((n_slots,), jnp.int32, rep), _sds((n_slots,), jnp.int32, rep),
        _sds((n_slots, 2), jnp.uint32, rep),
        _sds((n_slots,), jnp.float32, rep),
        _sds((n_slots,), jnp.bool_, rep)).compile().as_text()   # live mask
    assert "HloModule jit_serving_tick" in tick
    assert tick.count("%decode_attn_mla") >= layers
    _assert_resident_expert_layers(tick, 1)
    _assert_scopes(tick, "tick/layer/block/mla/proj",
                   "tick/layer/block/mla/cache_write",
                   "block/mla/core/tick/work_list", "decode_attn_mla",
                   "tick/layer/block/mlp/block/moe/route",
                   "block/mlp/block/moe/dispatch", "block/mlp/block/moe/gmm",
                   "block/mlp/block/moe/shared", tick=True)
    _assert_pool_written_in_place(tick, (n_slots, total, 640))
    _assert_tick_writes_rows_in_place(tick, layers)

    prefill = eng._build_prefill(prompt).lower(
        p, caches, _sds((1, prompt), jnp.int32, rep),
        _sds((), jnp.int32, rep), _sds((), jnp.int32, rep),
        _sds((2,), jnp.uint32, rep), _sds((), jnp.float32, rep)
    ).compile().as_text()
    assert f"HloModule jit_serving_prefill_{prompt}" in prefill
    assert prefill.count("%flash_fwd") >= layers
    # two kernels an expert layer where three (ISSUE 47)
    assert _moe_kernel_calls(prefill) == {"moe_gmm_glu": 1, "moe_gmm": 1}
    _assert_scopes(prefill, "prefill/embed", "prefill/head",
                   "block/mla/proj", "block/mla/core", "cache_write",
                   "block/moe/route", "block/moe/dispatch", "block/moe/gmm")
    _assert_pool_written_in_place(prefill, (n_slots, total, 640))


def _kimi_programs(topo, n_layers, prompts):
    """The serving programs of the benchmark's ``kimi-linear-48b-ep16``
    configuration (every width as published, the chip's share of experts
    and vocabulary, 64 slots of 4096 rows) with its first ``n_layers``
    layers: ``(arch, layout, tick, {prompt: prefill})`` compiled."""
    import importlib.util
    import json

    from chainermn_tpu._compat import shard_map
    from chainermn_tpu.parallel import blocks
    from chainermn_tpu.parallel.blocks import (KDAConfig, LMArch, MLAConfig,
                                               MoEConfig)
    from chainermn_tpu.serving.engine import DecodeEngine, result_size

    here = os.path.dirname(os.path.abspath(__file__))
    spec = importlib.util.spec_from_file_location(
        "kimi_reference", os.path.join(here, "kimi_linear_reference.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    with open(os.path.join(os.path.dirname(here), "benchmark", "configs",
                           "kimi-linear-48b-ep16.json")) as f:
        cfg = dict(json.load(f), num_hidden_layers=n_layers)
    lin = cfg["linear_attn_config"]
    arch = LMArch(
        norm="rmsnorm", norm_eps=cfg["rms_norm_eps"], mlp="swiglu",
        attn="mla", tied_head=False, embed_scale=False,
        attn_kinds=tuple("kda" if i + 1 in lin["kda_layers"] else "mla"
                         for i in range(n_layers)),
        layer_kinds=tuple("dense" if i < cfg["first_k_dense_replace"]
                          else "moe" for i in range(n_layers)),
        kda=KDAConfig(lin["num_heads"], lin["head_dim"],
                      lin["short_conv_kernel_size"], cfg["kda_gate_rank"]),
        mla=MLAConfig(cfg["num_attention_heads"], cfg["q_lora_rank"],
                      cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                      cfg["qk_rope_head_dim"], cfg["v_head_dim"],
                      rope=not cfg["mla_use_nope"]),
        moe=MoEConfig(cfg["num_experts"], cfg["num_experts_per_token"],
                      cfg["num_expert_group"], cfg["topk_group"],
                      cfg["routed_scaling_factor"], cfg["moe_renormalize"],
                      (0, cfg["num_experts_held"])))
    n_slots, total = 64, 4096
    mesh = Mesh(np.array(topo.devices[:1]), ("model",))
    rep = NamedSharding(mesh, P())
    shapes = jax.eval_shape(
        lambda k: ref.init_params(k, cfg, jnp.bfloat16), jax.random.PRNGKey(0))
    specs = blocks.lm_specs(arch, shapes, "model")
    p = jax.tree_util.tree_map(
        lambda x, sp: _sds(x.shape, x.dtype, NamedSharding(mesh, sp)),
        shapes, specs)
    layout = blocks.cache_layout(arch, n_layers, 0, "model")
    caches = [tuple(
        _sds((n_slots,) + tuple(b[0]), b[1] or jnp.bfloat16,
             NamedSharding(mesh, b[2])) if blocks.is_state(b)
        else _sds((n_slots, total, b[0]), jnp.bfloat16,
                  NamedSharding(mesh, b[1])) for b in bufs)
        for bufs in layout]
    eng = DecodeEngine.__new__(DecodeEngine)
    eng.mesh, eng.axis_name, eng.arch = mesh, "model", arch
    eng.head_dim = cfg["v_head_dim"]
    eng.n_counts = blocks.n_count_entries(arch)
    eng._specs, eng._shard_map, eng._P = specs, shard_map, P
    eng._cache_specs = [tuple(b[-1] for b in bufs) for bufs in layout]
    tick = eng._build_tick().lower(
        p, caches, _sds((result_size(eng.arch, n_slots),), jnp.int32, rep),
        _sds((n_slots,), jnp.int32, rep), _sds((n_slots,), jnp.int32, rep),
        _sds((n_slots, 2), jnp.uint32, rep),
        _sds((n_slots,), jnp.float32, rep),
        _sds((n_slots,), jnp.bool_, rep)).compile()          # live mask
    prefills = {
        s: eng._build_prefill(s).lower(
            p, caches, _sds((1, s), jnp.int32, rep),
            _sds((), jnp.int32, rep), _sds((), jnp.int32, rep),
            _sds((2,), jnp.uint32, rep), _sds((), jnp.float32, rep)
        ).compile() for s in prompts}
    return arch, layout, tick, prefills


def test_state_and_row_layers_in_one_pool_serving_programs(topo, as_tpu):
    """The serving programs of a model that keeps a recurrent STATE a slot
    in most layers (gated delta rule) and a latent ROW a token in the
    others, at Kimi-Linear-48B's published widths (the benchmark's
    ``kimi-linear-48b-ep16``).  The TICK at full depth, 27 layers: the
    ``conv_step`` kernel over the convolution window then the ``kda_step``
    kernel over the float32 state (20 layers each, one sort of the slots
    for all of them, no loop over the slots and no window laid out anew),
    the absorbed flash-decode kernel over the 640-column latent pool (7),
    the grouped expert product (26); both kinds of buffer written in place;
    weights + pool + temporaries inside one v5e chip.  The widest PREFILL (2048) at
    one period of the pattern behind the dense first layer (K K K M; its
    temporaries are a layer's, whatever the depth): the chunked delta rule
    is plain XLA, the latent layer takes the flash kernel at 192/128.
    Kernels, programs and scopes keep the names the trace readers match."""
    arch, layout, tick, _ = _kimi_programs(topo, 27, ())
    assert sum(arch.attn_kind(i) == "kda" for i in range(27)) == 20
    assert [[b[0] for b in bufs] for bufs in layout[2:4]] == [
        [(32, 128, 128), (3, 12288)], [640]]
    text, mem = tick.as_text(), tick.memory_analysis()
    assert "HloModule jit_serving_tick" in text
    calls = [ln.split(" = ")[0] for ln in text.split("\n")
             if "tpu_custom_call" in ln]
    count = lambda name: sum(name in c for c in calls)
    assert count("kda_step") == 20 and count("decode_attn_mla") == 7
    assert count("conv_step") == 20
    _assert_resident_expert_layers(text, 26)
    # no reader of an accepted metric may match the new kernels by substring
    for name in ("kda_step", "conv_step"):
        assert not any(n in name for n in ("decode_attn", "moe_gmm", "flash",
                                           "cache_write", "ssm_step"))
    assert "kda_step" not in "conv_step"
    # one busy list a tick, whatever the layers; the window neither walked
    # slot by slot (the vmapped dynamic_slice's loop until PR 41) nor laid
    # out anew around the kernel, and no (N, W, C) concatenation of it
    assert sum(" sort(" in ln and "s32[64]" in ln
               for ln in text.split("\n")) == 1
    assert not [ln for ln in text.split("\n") if " while(" in ln
                and "12288" in ln]
    assert not [ln for ln in text.split("\n") if " copy(" in ln and (
        "bf16[64,3,12288]" in ln or "bf16[3,64,12288]" in ln)]
    assert "bf16[64,4,12288]" not in text
    _assert_scopes(text, "tick/layer/block/kda/proj",
                   "tick/layer/block/kda/conv/jit(conv_step)/conv_step",
                   "tick/layer/block/kda/gate",
                   "block/kda/state_update/jit(kda_step)/kda_step",
                   "tick/layer/block/mla/proj", "block/mla/cache_write",
                   "block/mla/core/tick/work_list",
                   "tick/layer/block/mlp/block/moe/route",
                   "block/moe/dispatch", "block/moe/gmm", "block/moe/shared",
                   tick=True)
    _assert_pool_written_in_place(text, (64, 4096, 640))
    _assert_tick_writes_rows_in_place(text, 7)
    import re
    assert not re.findall(r"= f32\[64,32,128,128\]\S* copy\(", text)
    # 8.59 GB of weights + 5.13 GB of pool (state 2.78, rows 2.35)
    assert 13.6e9 < mem.argument_size_in_bytes < 13.8e9
    assert mem.temp_size_in_bytes < 0.3e9

    _, _, _, prefills = _kimi_programs(topo, 4, (2048,))
    pre, pmem = prefills[2048].as_text(), prefills[2048].memory_analysis()
    assert "HloModule jit_serving_prefill_2048" in pre
    assert pre.count("%flash_fwd") >= 1
    # two kernels an expert layer where three (ISSUE 47)
    calls = _moe_kernel_calls(pre)
    assert set(calls) == {"moe_gmm_glu", "moe_gmm"}
    assert calls["moe_gmm_glu"] == calls["moe_gmm"] >= 1
    assert "kda_step" not in pre            # the chunked form, not the step
    assert "conv_step" not in pre           # _short_conv over the prompt
    _assert_scopes(pre, "block/kda/proj", "block/kda/conv",
                   "block/kda/gate", "block/kda/state_update",
                   "block/mla/core", "prefill/head")
    _assert_pool_written_in_place(pre, (64, 4096, 640))
    # the whole model's arguments with the widest prefill's temporaries:
    # under 15.0 GB, the line ISSUE 31 draws for 64 slots
    assert mem.argument_size_in_bytes + pmem.temp_size_in_bytes < 15.0e9


def _laguna_programs(topo, n_layers, prompts, tick=True):
    """The serving programs of the benchmark's ``laguna-xs2-ep16``
    configuration (every width as published, the chip's share of experts,
    the whole vocabulary, 24 slots of 4096 rows) with its first
    ``n_layers`` layers: ``(arch, layout, tick, {prompt: prefill})``
    compiled."""
    import importlib.util
    import json

    from chainermn_tpu._compat import shard_map
    from chainermn_tpu.parallel import blocks
    from chainermn_tpu.serving.engine import DecodeEngine, result_size

    here = os.path.dirname(os.path.abspath(__file__))

    def load(name, path):
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    bench = os.path.join(os.path.dirname(here), "benchmark")
    ref = load("laguna_reference", os.path.join(here, "laguna_reference.py"))
    fam = load("laguna_family", os.path.join(bench, "families", "laguna.py"))
    with open(os.path.join(bench, "configs", "laguna-xs2-ep16.json")) as f:
        cfg = json.load(f)
    cfg = dict(cfg, num_hidden_layers=n_layers, **{
        k: cfg[k][:n_layers] for k in (
            "layer_types", "mlp_layer_types",
            "num_attention_heads_per_layer")})
    arch = fam.arch_of(cfg)
    n_slots, total = 24, 4096
    mesh = Mesh(np.array(topo.devices[:1]), ("model",))
    rep = NamedSharding(mesh, P())
    shapes = jax.eval_shape(
        lambda k: ref.init_params(k, cfg, jnp.bfloat16), jax.random.PRNGKey(0))
    specs = blocks.lm_specs(arch, shapes, "model")
    p = jax.tree_util.tree_map(
        lambda x, sp: _sds(x.shape, x.dtype, NamedSharding(mesh, sp)),
        shapes, specs)
    layout = blocks.cache_layout(arch, n_layers, 8 * 128, "model")
    caches = [tuple(_sds(blocks.buffer_shape(b, n_slots, total),
                         jnp.bfloat16, NamedSharding(mesh, b[1]))
                    for b in bufs) for bufs in layout]
    eng = DecodeEngine.__new__(DecodeEngine)
    eng.mesh, eng.axis_name, eng.arch = mesh, "model", arch
    eng.head_dim = cfg["head_dim"]
    eng.n_counts = blocks.n_count_entries(arch)
    eng._specs, eng._shard_map, eng._P = specs, shard_map, P
    eng._cache_specs = [tuple(b[1] for b in bufs) for bufs in layout]
    tick = eng._build_tick().lower(
        p, caches, _sds((result_size(eng.arch, n_slots),), jnp.int32, rep),
        _sds((n_slots,), jnp.int32, rep), _sds((n_slots,), jnp.int32, rep),
        _sds((n_slots, 2), jnp.uint32, rep),
        _sds((n_slots,), jnp.float32, rep),
        _sds((n_slots,), jnp.bool_, rep)).compile() if tick else None
    prefills = {
        s: eng._build_prefill(s).lower(
            p, caches, _sds((1, s), jnp.int32, rep),
            _sds((), jnp.int32, rep), _sds((), jnp.int32, rep),
            _sds((2,), jnp.uint32, rep), _sds((), jnp.float32, rep)
        ).compile() for s in prompts}
    return arch, layout, tick, prefills


def test_ring_and_row_layers_in_one_pool_serving_programs(topo, as_tpu):
    """The serving programs of a model whose sliding-window GQA layers keep
    a RING of 512 rows a slot (64 query heads) and whose full-attention
    layers keep every row (48), 8 KV heads of 128, at Laguna-XS.2's
    published widths (the benchmark's ``laguna-xs2-ep16``), at FULL depth,
    40 layers, and the whole 100,352-row vocabulary.  The TICK: the GQA
    flash-decode kernel through the one-position-per-slot face in every
    layer, over rows and rings alike (no beam kernel), the grouped expert
    product (39); both kinds of buffer written in place; weights + pool +
    temporaries inside one v5e chip.  The widest PREFILL (3072): the banded
    flash forward in the 30 sliding layers, the causal one in the 10 full.
    Kernels, programs and scopes keep the names the trace readers match."""
    arch, layout, tick, prefills = _laguna_programs(topo, 40, (3072,))
    assert sum(bool(arch.window(i)) for i in range(40)) == 30
    assert [tuple(b[0::2] if len(b) == 3 else b[:1] for b in bufs)
            for bufs in layout[:2]] == [((1024,),) * 2, ((1024, 512),) * 2]
    text, mem = tick.as_text(), tick.memory_analysis()
    assert "HloModule jit_serving_tick" in text
    calls = [ln.split(" = ")[0] for ln in text.split("\n")
             if "tpu_custom_call" in ln]
    count = lambda name: sum(name in c for c in calls)
    assert count("decode_attn_gqa") == 40 and count("decode_attn_beam") == 0
    assert count("decode_attn") == 40       # what decode_attn_ms_per_tick sums
    _assert_resident_expert_layers(text, 39)
    # ... and nothing else between the routing's index work and the layer's
    # sum: no gather, no operation over an (m_pad, D) = (704, 2048) buffer
    under = [ln for ln in text.split("\n") if "block/moe/gmm" in ln]
    assert len(under) >= 2 * 39
    assert not [ln for ln in under if " gather(" in ln or "[704,2048]" in ln]
    # no reader of another model's metric may match the new names
    for name in ("decode_attn_gqa", "window_flash_fwd"):
        assert not any(n in name for n in ("mla", "moe_gmm", "kda"))
    _assert_scopes(text, "tick/layer/block/attn/proj",
                   "block/attn/core/block/attn/window/cache_write",
                   "tick/layer/block/attn/core/cache_write",
                   "block/attn/core/tick/work_list", "decode_attn_gqa",
                   "tick/layer/block/attn/gate",
                   "tick/layer/block/mlp/block/moe/dispatch", tick=True)
    _assert_pool_written_in_place(text, (24, 4096, 1024))
    _assert_pool_written_in_place(text, (24, 512, 1024))
    _assert_tick_writes_rows_in_place(text, 40)
    # 8.0 GB of weights + 5.54 GB of pool (rows 4.03, rings 1.51)
    assert 13.5e9 < mem.argument_size_in_bytes < 13.6e9
    assert mem.temp_size_in_bytes < 0.3e9

    pre, pmem = prefills[3072].as_text(), prefills[3072].memory_analysis()
    assert "HloModule jit_serving_prefill_3072" in pre
    assert pre.count("%window_flash_fwd") >= 30
    assert pre.count("%flash_fwd") >= 10
    assert _moe_kernel_calls(pre) == {"moe_gmm_glu": 39, "moe_gmm": 39}
    assert "decode_attn" not in pre
    _assert_scopes(pre, "block/attn/core/block/attn/window",
                   "block/attn/gate", "cache_write", "block/attn/proj",
                   "prefill/embed")
    _assert_pool_written_in_place(pre, (24, 4096, 1024))
    _assert_pool_written_in_place(pre, (24, 512, 1024))
    # arguments plus the widest prefill's temporaries: under 15.0 GB, the
    # line ISSUE 33 draws for 24 slots
    assert pmem.argument_size_in_bytes + pmem.temp_size_in_bytes < 15.0e9


@pytest.mark.parametrize("kernel", ["ssm_step", "selective_scan_256",
                                    "selective_scan_1024"])
def test_selective_state_kernels_at_the_published_widths(one_chip, kernel):
    """``ops/ssm_step.py`` over a pool of 128 slots and
    ``ops/selective_scan.py`` over the shortest and the longest prefill
    bucket, at Jamba2-3B's widths (5120 channels, 16 states): the chip's
    compiler takes the ``(16, 40, 128)`` state layout, the scalar-memory
    ``B`` and ``C`` (128 KB at 1024 tokens) and the in-kernel token loop;
    the step's state is aliased to its result."""
    from chainermn_tpu.ops.selective_scan import selective_scan
    from chainermn_tpu.ops.ssm_step import ssm_step

    f32 = lambda *shape: _sds(shape, jnp.float32, one_chip)
    e, n = 5120, 16
    if kernel == "ssm_step":
        slots = 128
        compiled = jax.jit(partial(ssm_step, interpret=False),
                           donate_argnums=(6,)).lower(
            f32(slots, e), f32(slots, e), f32(slots, n), f32(slots, n),
            f32(n, e), f32(e), f32(slots, n, 40, 128),
            _sds((slots,), jnp.bool_, one_chip)).compile()
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes == slots * n * e * 4
    else:
        s = int(kernel.rsplit("_", 1)[1])
        compiled = jax.jit(partial(selective_scan, interpret=False)).lower(
            f32(1, s, e), f32(1, s, e), f32(1, s, n), f32(1, s, n),
            f32(n, e), f32(e), f32(1, n, 40, 128),
            _sds((1,), jnp.int32, one_chip)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert f"%{kernel.rsplit('_', 1)[0] if 'scan' in kernel else kernel}" \
        in text


def _jamba_programs(topo, n_layers, prompts, tick=True):
    """The serving programs of the benchmark's ``jamba2-3b`` configuration
    (every width as published, the whole vocabulary, 128 slots of 2048
    rows) with its first ``n_layers`` layers: ``(arch, layout, tick,
    {prompt: prefill})`` compiled."""
    import importlib.util
    import json

    from chainermn_tpu._compat import shard_map
    from chainermn_tpu.parallel import blocks
    from chainermn_tpu.parallel.blocks import LMArch, MambaConfig
    from chainermn_tpu.serving.engine import DecodeEngine, result_size

    here = os.path.dirname(os.path.abspath(__file__))
    spec = importlib.util.spec_from_file_location(
        "jamba_reference", os.path.join(here, "jamba_reference.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    with open(os.path.join(os.path.dirname(here), "benchmark", "configs",
                           "jamba2-3b.json")) as f:
        cfg = dict(json.load(f), num_hidden_layers=n_layers)
    arch = LMArch(
        norm="rmsnorm", norm_eps=cfg["rms_norm_eps"], mlp="swiglu",
        attn="mha", tied_head=True, embed_scale=False, attn_bias=False,
        positions=False,
        attn_kinds=tuple("mha" if ref.is_attention(cfg, i) else "mamba"
                         for i in range(n_layers)),
        mamba=MambaConfig(cfg["mamba_expand"] * cfg["hidden_size"],
                          cfg["mamba_d_state"], cfg["mamba_d_conv"],
                          cfg["mamba_dt_rank"]))
    n_slots, total = 128, 2048
    mesh = Mesh(np.array(topo.devices[:1]), ("model",))
    rep = NamedSharding(mesh, P())
    shapes = jax.eval_shape(
        lambda k: ref.init_params(k, cfg, jnp.bfloat16), jax.random.PRNGKey(0))
    specs = blocks.lm_specs(arch, shapes, "model")
    p = jax.tree_util.tree_map(
        lambda x, sp: _sds(x.shape, x.dtype, NamedSharding(mesh, sp)),
        shapes, specs)
    layout = blocks.cache_layout(arch, n_layers, 128, "model")
    caches = [tuple(
        _sds(blocks.buffer_shape(b, n_slots, total),
             (b[1] if blocks.is_state(b) else None) or jnp.bfloat16,
             NamedSharding(mesh, b[2] if blocks.is_state(b) else b[1]))
        for b in bufs) for bufs in layout]
    eng = DecodeEngine.__new__(DecodeEngine)
    eng.mesh, eng.axis_name, eng.arch = mesh, "model", arch
    eng.head_dim = 128
    eng.n_counts = blocks.n_count_entries(arch)
    eng._specs, eng._shard_map, eng._P = specs, shard_map, P
    eng._cache_specs = [tuple(b[2] if blocks.is_state(b) else b[1]
                              for b in bufs) for bufs in layout]
    tick = eng._build_tick().lower(
        p, caches, _sds((result_size(eng.arch, n_slots),), jnp.int32, rep),
        _sds((n_slots,), jnp.int32, rep), _sds((n_slots,), jnp.int32, rep),
        _sds((n_slots, 2), jnp.uint32, rep),
        _sds((n_slots,), jnp.float32, rep),
        _sds((n_slots,), jnp.bool_, rep)).compile() if tick else None
    prefills = {
        s: eng._build_prefill(s).lower(
            p, caches, _sds((1, s), jnp.int32, rep),
            _sds((), jnp.int32, rep), _sds((), jnp.int32, rep),
            _sds((2,), jnp.uint32, rep), _sds((), jnp.float32, rep)
        ).compile() for s in prompts}
    return arch, layout, tick, prefills


def test_selective_state_and_row_layers_in_one_pool_serving_programs(
        topo, as_tpu):
    """The serving programs of a model that keeps a selective-scan STATE a
    slot in 26 layers and a multi-query ``(k, v)`` ROW a token in 2, with no
    positions, at AI21-Jamba2-3B's published widths (the benchmark's
    ``jamba2-3b``), WHOLE: 28 layers, 65536 rows.  The TICK: ``ssm_step``
    over the float32 state (26 layers), the GQA flash-decode kernel at
    group 20 on one KV head (2), the row writer (2); both kinds of buffer
    written in place; weights + pool + temporaries inside one v5e chip.
    The widest PREFILL (1024): ``selective_scan`` in the 26 state layers,
    the causal flash forward at one KV head in the 2 others, and NO ``(S,
    E, N)`` array anywhere — its temporaries are a few ``(S, E)`` float32
    arrays.  Kernels, programs and scopes keep the names the trace readers
    match."""
    import re

    arch, layout, tick, prefills = _jamba_programs(topo, 28, (1024,))
    assert [i for i in range(28) if arch.attn_kind(i) == "mha"] == [7, 21]
    assert [b[0] for b in layout[0]] == [(16, 40, 128), (3, 5120)]
    assert [b[0] for b in layout[7]] == [128, 128]
    text, mem = tick.as_text(), tick.memory_analysis()
    assert "HloModule jit_serving_tick" in text
    calls = [ln.split(" = ")[0] for ln in text.split("\n")
             if "tpu_custom_call" in ln]
    count = lambda name: sum(name in c for c in calls)
    assert count("ssm_step") == 26 and count("decode_attn_gqa") == 2
    assert count("decode_attn") == 2        # what decode_attn_ms_per_tick sums
    assert count("conv_step") == 0          # its window is _short_conv's still
    # no reader of an accepted metric may match the new kernels by substring
    for name in ("ssm_step", "selective_scan"):
        assert not any(n in name for n in ("decode_attn", "moe_gmm", "kda",
                                           "mla", "flash", "fused_ce",
                                           "cache_write"))
    _assert_scopes(text, "tick/layer/block/mamba/proj",
                   "tick/layer/block/mamba/conv",
                   "block/mamba/core/jit(ssm_step)/ssm_step",
                   "tick/layer/block/attn/proj",
                   "tick/layer/block/attn/core/cache_write",
                   "block/attn/core/tick/work_list", "decode_attn_gqa",
                   "tick/layer/block/mlp", tick=True)
    _assert_pool_written_in_place(text, (128, 2048, 128))
    _assert_tick_writes_rows_in_place(text, 2)
    assert not re.findall(r"= f32\[128,16,40,128\]\S* copy\(", text)
    # 6.06 GB of weights + 1.46 GB of pool (state 1.19, rows 0.27)
    assert 7.4e9 < mem.argument_size_in_bytes < 7.7e9
    assert mem.temp_size_in_bytes < 0.3e9

    pre, pmem = prefills[1024].as_text(), prefills[1024].memory_analysis()
    assert "HloModule jit_serving_prefill_1024" in pre
    pre_calls = [ln.split(" = ")[0] for ln in pre.split("\n")
                 if "tpu_custom_call" in ln]
    assert sum("selective_scan" in c for c in pre_calls) == 26
    assert not any("ssm_step" in c for c in pre_calls)   # the scan, not the step
    assert pre.count("%flash_fwd") >= 2 and "decode_attn" not in pre
    _assert_scopes(pre, "block/mamba/proj", "block/mamba/conv",
                   "block/mamba/core/jit(selective_scan)/selective_scan",
                   "block/attn/core", "prefill/head")
    _assert_pool_written_in_place(pre, (128, 2048, 128))
    # NO (S, E, N) array: one would be 1024 x 5120 x 16 x 4 B = 335 MB;
    # the whole program's temporaries are far under one
    assert not re.findall(r"f32\[(1,)?1024,(5120,16|16,5120|16,40,128)\]",
                          pre)
    assert pmem.temp_size_in_bytes < 0.2e9
