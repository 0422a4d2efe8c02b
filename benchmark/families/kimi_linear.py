"""Kimi Linear family (``model_type`` ``kimi_linear``): builds the program's
serving engine through the program's public API (``chainermn_tpu``) from a
configuration file's keys, as ONE CHIP'S SHARE of an expert-parallel
deployment (the configuration's ``deployment``): gated delta-rule (KDA)
layers with a per-slot state beside latent-attention layers with a row a
token, sigmoid-routed experts.  The weights come from the reference's seeded
initialiser, so the program and the reference start from the same numbers
and neither takes anything the other made.  Serving only (PERF.md, section
4).  The driver-facing server and the comparison are the DeepSeek-V3
family's shape (``families/deepseek_v3.py``), copied, not imported: a
family stands alone."""

import gc
import os
import sys
import jax
import jax.numpy as jnp
import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(_HERE)))

from benchmark.harness import checks as _checks           # noqa: E402
from benchmark.harness.loader import module as _module   # noqa: E402

ref = _module("reference", "kimi_linear")
#: the driver-facing server scaffolding is the GPT-2 family's (the same
#: engine, another model)
_gpt2 = _module("families", "gpt2")

#: the configuration states bfloat16; the nearest precision below it
CONTROL_PRECISION = "fp8"


def _key(seed: int):
    return jax.random.PRNGKey(np.uint32(seed % (2 ** 32)))


def arch_of(cfg):
    """The program's description of the model (``parallel/blocks.py``),
    read from the configuration's published keys: the attention kind of
    each LAYER (the configuration numbers its layers from 1), queries
    projected directly (``q_lora_rank`` null), no rotation
    (``mla_use_nope``); this chip holds the first ``num_experts_held``
    routed experts (rank 0)."""
    from chainermn_tpu.parallel.blocks import (KDAConfig, LMArch, MLAConfig,
                                               MoEConfig)

    lin = cfg["linear_attn_config"]
    n, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    return LMArch(
        norm="rmsnorm", norm_eps=cfg["rms_norm_eps"], mlp="swiglu",
        attn="mla", tied_head=bool(cfg.get("tie_word_embeddings", False)),
        embed_scale=False,
        attn_kinds=tuple("kda" if i + 1 in lin["kda_layers"] else "mla"
                         for i in range(n)),
        layer_kinds=tuple("dense" if i < dense else "moe" for i in range(n)),
        kda=KDAConfig(
            n_heads=lin["num_heads"], head_dim=lin["head_dim"],
            conv_width=lin["short_conv_kernel_size"],
            gate_rank=cfg["kda_gate_rank"]),
        mla=MLAConfig(
            n_heads=cfg["num_attention_heads"],
            q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
            qk_nope_head_dim=cfg["qk_nope_head_dim"],
            qk_rope_head_dim=cfg["qk_rope_head_dim"],
            v_head_dim=cfg["v_head_dim"], rope_theta=float(cfg["rope_theta"]),
            rope=not cfg["mla_use_nope"]),
        moe=MoEConfig(
            n_experts=cfg["num_experts"],
            top_k=cfg["num_experts_per_token"],
            n_group=cfg["num_expert_group"], topk_group=cfg["topk_group"],
            routed_scaling_factor=cfg["routed_scaling_factor"],
            norm_topk_prob=cfg["moe_renormalize"],
            held=(0, cfg.get("num_experts_held", cfg["num_experts"]))))


def _tick_without_decay():
    """FAULT: the tick's state update skips the decay (``g = 0`` in the
    one-token step, kernel and plain twin alike); the prefill is sound."""
    from chainermn_tpu.ops import kda_step as ops

    real = {name: getattr(ops, name) for name in ("kda_step", "kda_step_xla")}
    for name, fn in real.items():
        setattr(ops, name, lambda q, k, v, g, *rest, _fn=fn, **kw: _fn(
            q, k, v, jnp.zeros_like(g), *rest, **kw))
    return lambda: [setattr(ops, name, fn) for name, fn in real.items()]


def _prefill_state_at_the_padded_length():
    """FAULT: the prefill is not told which rows of a padded prompt are
    real, so the state (and the convolution window) it hands the pool
    stand at ``s_pad``, after the padding; the tick is sound."""
    from chainermn_tpu.parallel import kda

    real = kda.kda_project
    kda.kda_project = lambda cfg, h, a, window, live: real(
        cfg, h, a, window, None if h.shape[1] > 1 else live)
    return lambda: setattr(kda, "kda_project", real)


#: broken-state programs that ``correct`` must tell from the sound one
#: (``benchmark/state_control.py``): name -> a function that breaks the
#: program in place and returns the function that mends it.  A logit check
#: can be blind to a state (seeded GPT-2 weights once were: PERF.md,
#: Findings PR 24), so these are run, at the tiny size under
#: ``benchmark/tests`` and on the chip
STATE_FAULTS = {"tick_without_decay": _tick_without_decay,
                "prefill_state_at_s_pad": _prefill_state_at_the_padded_length}


def _mesh(ctx):
    import chainermn_tpu as mn

    return mn.make_nd_mesh(("model",), (1,), ctx.devices[:1])


def _weights(ctx, mesh):
    """bfloat16 weights made on the device from the seed, each leaf placed
    where the engine wants it (so the engine's own placement copies
    nothing: 8.6 GB of weights cannot be held twice)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    return ref.init_params(_key(ctx.seed), ctx.config, jnp.bfloat16,
                           put=NamedSharding(mesh, P()))


class Server(_gpt2.Server):
    """The program's ``ServingEngine`` at the configuration's sizes: the
    GPT-2 family's driver-facing server (``warm``, ``submit``, ``idle``,
    ``backlog``, ``metrics``) around another model."""

    def __init__(self, ctx):
        from chainermn_tpu.serving import ServingEngine

        cfg, eng = ctx.config, dict(ctx.traffic["engine"])
        self.vocab = cfg["vocab_size"]
        # first: a program without the block description (a parent commit)
        # fails here, before a minute of drawing weights
        arch = arch_of(cfg)
        mesh = _mesh(ctx)
        params = _weights(ctx, mesh)
        self.eng = ServingEngine(
            params, head_dim=cfg["v_head_dim"], mesh=mesh, arch=arch, **eng)
        self._weights = params      # the leaves made here: freed in close()
        pool = self.eng.pool
        self.info = {"engine": eng, "prefix_cache": True,
                     "cache_bytes_per_token": pool.bytes_per_token,
                     "cache_state_bytes_per_slot": pool.state_bytes_per_slot}

    def step(self):
        # the program bounds a free slot's position itself since PR 27, so
        # the GPT-2 family's workaround is not inherited
        self.eng.step()

    def close(self):
        """Retire the engine and free its device memory NOW: the reference
        draws the same 8.6 GB of weights again, and an engine waiting for the
        collector to find its cycles still holds the first copy and the
        pool."""
        eng, self.eng = self.eng, None
        eng.close()
        held = jax.tree_util.tree_leaves((self._weights, eng.pool.caches))
        self._weights = None
        del eng
        gc.collect()
        for leaf in held:
            if not leaf.is_deleted():
                leaf.delete()


def build_server(ctx):
    return Server(ctx)


def served_sample(ctx, recs, reqs, k: int):
    """``k`` finished requests drawn from the seed, the longest among them
    (as the GPT-2 family draws them): each as (prompt + emitted tokens,
    prompt length, the experts the serving programs chose for each emitted
    token — ``RequestHandle.routes``, what the window's own prefills and
    ticks read back)."""
    done = [i for i, r in enumerate(recs) if r["handle"] is not None
            and r["handle"].status == "done"]
    if not done:
        return []
    length = lambda i: len(reqs[i]["prompt"]) + len(recs[i]["handle"].tokens)
    longest = max(done, key=length)
    rest = [i for i in done if i != longest]
    rng = np.random.default_rng(ctx.seed)
    picked = [longest] + list(rng.permutation(rest)[: k - 1])
    return [(np.concatenate([reqs[i]["prompt"], np.asarray(
        recs[i]["handle"].tokens, np.int32)]), len(reqs[i]["prompt"]),
        np.asarray(recs[i]["handle"].routes, np.int32)) for i in picked]


def serve_compare(ctx, sample, precision=None):
    """The reference's one full forward over each sampled prompt with its
    served tokens (after the engine is freed), read three ways
    (``reference/kimi_linear.py::LIMITS`` says why): the mean gap by which
    a served token's logit lies below the reference's best, the share of
    the served routing that is not the reference's, and the share of
    served tokens that are not the reference's first."""
    cfg = ctx.config
    lim = ref.LIMITS
    if not sample:
        return [_checks.row("served_logit_gap", float("nan"),
                            lim["served_logit_gap"])]
    width = ctx.traffic["engine"]["max_total"] + 1
    tokens = np.zeros((len(sample), width), np.int32)
    for i, (seq, _, _) in enumerate(sample):
        tokens[i, : len(seq)] = seq
    params = _weights(ctx, _mesh(ctx))
    with jax.default_matmul_precision("highest"):
        got = ref.served_gaps(
            params, cfg, tokens, [p for _, p, _ in sample],
            [len(s) for s, _, _ in sample],
            program_routes=[r for _, _, r in sample], precision=precision)
    ctx.say(f"reference: {len(sample)} served requests, {got['n']} served "
            f"tokens, exact argmax agreement {got['agree']:.4f}, gap mean "
            f"{got['gap_mean']:.4g}, widest {got['gap_max']:.4g}, widest "
            f"where the routes agree {got['gap_max_agreeing']:.4g}")
    return [_checks.row("served_logit_gap", got["gap_mean"],
                        lim["served_logit_gap"]),
            _checks.row("route_disagreement", got["disagreement"],
                        lim["route_disagreement"]),
            _checks.row("argmax_disagreement", 1.0 - got["agree"],
                        lim["argmax_disagreement"])]
