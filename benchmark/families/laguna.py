"""Laguna family (``model_type`` ``laguna``): builds the program's serving
engine through the program's public API (``chainermn_tpu``) from a
configuration file's keys, as ONE CHIP'S SHARE of an expert-parallel
deployment (the configuration's ``deployment``): sliding-window GQA layers
that keep a ring of their window's rows a slot beside full-attention GQA
layers that keep every row, a head count and rotary parameters by layer, a
gated attention output, sigmoid-routed experts.  The weights come from the
reference's seeded initialiser, so the program and the reference start from
the same numbers and neither takes anything the other made.  Serving only
(PERF.md, section 4).  The driver-facing server and the comparison are the
DeepSeek-V3 family's shape (``families/deepseek_v3.py``), copied, not
imported: a family stands alone."""

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

ref = _module("reference", "laguna")
#: the driver-facing server scaffolding is the GPT-2 family's (the same
#: engine, another model)
_gpt2 = _module("families", "gpt2")

#: the configuration states bfloat16; the nearest precision below it
CONTROL_PRECISION = "fp8"
#: of the checked requests, those served past this many tokens of context
#: (twice the window: a full-attention answer and a windowed one have
#: parted by then) are at least half
LONG_CONTEXT = 1024


def _key(seed: int):
    return jax.random.PRNGKey(np.uint32(seed % (2 ** 32)))


def arch_of(cfg):
    """The program's description of the model (``parallel/blocks.py``),
    read from the configuration's published keys, a value a LAYER where the
    configuration gives one: the window (``layer_types``), the rotation
    (``rope_parameters`` by layer kind), dense MLP or experts
    (``mlp_layer_types``); the query-head count is the weights'.  This chip
    holds the first ``num_experts_held`` routed experts (rank 0)."""
    from chainermn_tpu.parallel.blocks import LMArch, MoEConfig, Rotary

    def rotary(rp):
        yarn = rp["rope_type"] == "yarn"
        return Rotary(
            theta=float(rp["rope_theta"]),
            fraction=rp["partial_rotary_factor"],
            yarn=(rp["factor"], rp["original_max_position_embeddings"],
                  rp["beta_fast"], rp["beta_slow"]) if yarn else None,
            attention_factor=rp["attention_factor"] if yarn else 1.0)

    kinds = {kind: rotary(rp) for kind, rp in cfg["rope_parameters"].items()
             if isinstance(rp, dict)}
    return LMArch(
        norm="rmsnorm", norm_eps=cfg["rms_norm_eps"], mlp="swiglu",
        attn="mha", tied_head=bool(cfg.get("tie_word_embeddings", False)),
        embed_scale=False,
        layer_kinds=tuple("moe" if t == "sparse" else "dense"
                          for t in cfg["mlp_layer_types"]),
        windows=tuple(cfg["sliding_window"] if t == "sliding_attention"
                      else None for t in cfg["layer_types"]),
        rotary=tuple(kinds[t] for t in cfg["layer_types"]),
        attn_gate=bool(cfg["gating"]), attn_bias=bool(cfg["attention_bias"]),
        moe=MoEConfig(
            n_experts=cfg["num_experts"], top_k=cfg["num_experts_per_tok"],
            n_group=1, topk_group=1,
            routed_scaling_factor=cfg["moe_routed_scaling_factor"],
            norm_topk_prob=True,
            held=(0, cfg.get("num_experts_held", cfg["num_experts"]))))


def _sliding_layers_see_their_whole_prefix():
    """FAULT: the prefill's sliding layers attend causally with no band,
    so every prompt position past the window is computed from keys it may
    not see (and its rows and ring go on from there); the tick is sound."""
    import importlib

    # (``chainermn_tpu.ops.flash_attention`` the attribute is the function)
    ops = importlib.import_module("chainermn_tpu.ops.flash_attention")
    real = ops.flash_attention
    ops.flash_attention = lambda *a, window=None, **kw: real(*a, **kw)
    return lambda: setattr(ops, "flash_attention", real)


def _ring_filled_from_the_padded_length():
    """FAULT: the prefill is not told which rows of a padded prompt are
    real when it leaves the ring, so the ring holds the rows before
    ``s_pad`` — padded rows on real ones' places; the tick is sound."""
    from chainermn_tpu.parallel import blocks

    real = blocks.ring_rows
    blocks.ring_rows = lambda rows, s_real, window: real(
        rows, jnp.full_like(s_real, rows.shape[1]), window)
    return lambda: setattr(blocks, "ring_rows", real)


#: broken-WINDOW programs that ``correct`` must tell from the sound one
#: (``benchmark/state_control.py`` runs whatever a family names here: a
#: ring is per-slot state of its own kind): name -> a function that breaks
#: the program in place and returns the function that mends it.  A logit
#: check can be blind to a window (a full-attention answer at 600 tokens of
#: context is close to the windowed one), so these are run, at the tiny
#: size under ``benchmark/tests`` and on the chip
STATE_FAULTS = {"sliding_sees_whole_prefix":
                _sliding_layers_see_their_whole_prefix,
                "ring_filled_from_s_pad": _ring_filled_from_the_padded_length}


def _mesh(ctx):
    import chainermn_tpu as mn

    return mn.make_nd_mesh(("model",), (1,), ctx.devices[:1])


def _weights(ctx, mesh):
    """bfloat16 weights made on the device from the seed, each leaf placed
    where the engine wants it (so the engine's own placement copies
    nothing: 8 GB of weights cannot be held twice)."""
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
            params, head_dim=cfg["head_dim"], mesh=mesh, arch=arch, **eng)
        self._weights = params      # the leaves made here: freed in close()
        pool = self.eng.pool
        self.info = {"engine": eng, "prefix_cache": True,
                     "cache_bytes_per_token": pool.bytes_per_token,
                     "cache_ring_bytes_per_slot": pool.ring_bytes_per_slot,
                     "pool_bytes": pool.n_slots * (
                         pool.max_total * pool.bytes_per_token
                         + pool.ring_bytes_per_slot)}

    def step(self):
        # the program bounds a free slot's position itself since PR 27, so
        # the GPT-2 family's workaround is not inherited
        self.eng.step()

    def close(self):
        """Retire the engine and free its device memory NOW: the reference
        draws the same 8 GB of weights again, and an engine waiting for the
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
    """``k`` finished requests drawn from the seed — the longest among
    them, and at least half of them served past ``LONG_CONTEXT`` tokens of
    context, where a window shows (fewer only if the window finished
    fewer) — each as (prompt + emitted tokens, prompt length, the experts
    the serving programs chose for each emitted token —
    ``RequestHandle.routes``, what the window's own prefills and ticks read
    back)."""
    done = [i for i, r in enumerate(recs) if r["handle"] is not None
            and r["handle"].status == "done"]
    if not done:
        return []
    length = lambda i: len(reqs[i]["prompt"]) + len(recs[i]["handle"].tokens)
    rng = np.random.default_rng(ctx.seed)
    order = [int(i) for i in rng.permutation(done)]
    longest = max(done, key=length)
    long = [i for i in order if length(i) > LONG_CONTEXT and i != longest]
    picked = [longest] + long[: k // 2 - 1]
    picked += [i for i in order if i not in picked][: k - len(picked)]
    return [(np.concatenate([reqs[i]["prompt"], np.asarray(
        recs[i]["handle"].tokens, np.int32)]), len(reqs[i]["prompt"]),
        np.asarray(recs[i]["handle"].routes, np.int32)) for i in picked]


def serve_compare(ctx, sample, precision=None):
    """The reference's one full forward over each sampled prompt with its
    served tokens (after the engine is freed), read three ways
    (``reference/laguna.py::LIMITS`` says why): the mean gap by which
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
    long = sum(len(s) > LONG_CONTEXT for s, _, _ in sample)
    ctx.say(f"reference: {len(sample)} served requests ({long} past "
            f"{LONG_CONTEXT} tokens of context), {got['n']} served "
            f"tokens, exact argmax agreement {got['agree']:.4f}, gap mean "
            f"{got['gap_mean']:.4g}, widest {got['gap_max']:.4g}, widest "
            f"where the routes agree {got['gap_max_agreeing']:.4g}")
    return [_checks.row("served_logit_gap", got["gap_mean"],
                        lim["served_logit_gap"]),
            _checks.row("route_disagreement", got["disagreement"],
                        lim["route_disagreement"]),
            _checks.row("argmax_disagreement", 1.0 - got["agree"],
                        lim["argmax_disagreement"])]
