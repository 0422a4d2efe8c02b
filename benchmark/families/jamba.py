"""Jamba family (``model_type`` ``jamba``): builds the program's serving
engine through the program's public API (``chainermn_tpu``) from a
configuration file's keys, WHOLE on one chip: Mamba-1 selective state-space
layers with a per-slot state beside multi-query attention layers with a row
a token, one dense SwiGLU a layer, no positional signal, a tied head.  The
weights come from the reference's seeded initialiser, so the program and
the reference start from the same numbers and neither takes anything the
other made.  Serving only (PERF.md, section 4: no cut of the model trains
on a chip).  The driver-facing server and the comparison are the Kimi Linear
family's shape (``families/kimi_linear.py``), copied, not imported: a
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

ref = _module("reference", "jamba")
#: the driver-facing server scaffolding is the GPT-2 family's (the same
#: engine, another model)
_gpt2 = _module("families", "gpt2")

#: the configuration states bfloat16 weights and a float32 state; the
#: control computes in the nearest precision below each: fp8 matmul
#: operands, a bfloat16 state (``reference/jamba.py``)
CONTROL_PRECISION = "fp8"
#: of the checked requests, two have a prompt past the first prefill bucket
#: and one past the third (ISSUE 40: the scan and the flash prefill at
#: more than one chunk, the state handed over mid-bucket)
PAST_BUCKETS = (768, 256)


def _key(seed: int):
    return jax.random.PRNGKey(np.uint32(seed % (2 ** 32)))


def arch_of(cfg):
    """The program's description of the model (``parallel/blocks.py``),
    read from the configuration's published keys: the kind of each LAYER
    (HF ``JambaConfig.layers_block_type``), no biases, no positions, the
    embedding not scaled, the head tied.  A program without the Mamba
    description (a parent commit) fails here, at once."""
    from chainermn_tpu.parallel.blocks import LMArch, MambaConfig

    if cfg["num_experts"] != 1:
        raise ValueError("this family serves the dense Jamba (num_experts "
                         f"1); the configuration has {cfg['num_experts']}")
    return LMArch(
        norm="rmsnorm", norm_eps=cfg["rms_norm_eps"], mlp="swiglu",
        attn="mha", tied_head=bool(cfg["tie_word_embeddings"]),
        embed_scale=False, attn_bias=False, positions=False,
        attn_kinds=tuple("mha" if ref.is_attention(cfg, i) else "mamba"
                         for i in range(cfg["num_hidden_layers"])),
        mamba=MambaConfig(
            d_inner=cfg["mamba_expand"] * cfg["hidden_size"],
            d_state=cfg["mamba_d_state"], conv_width=cfg["mamba_d_conv"],
            dt_rank=cfg["mamba_dt_rank"]))


def _tick_without_decay():
    """FAULT: the tick's state update skips the decay (rates 0 in the
    one-token step, kernel and plain twin alike: ``exp(dt * 0) = 1``); the
    prefill is sound."""
    from chainermn_tpu.ops import ssm_step as ops

    real = {name: getattr(ops, name) for name in ("ssm_step", "ssm_step_xla")}
    for name, fn in real.items():
        setattr(ops, name, lambda c, dt, b, cc, a, *rest, _fn=fn, **kw: _fn(
            c, dt, b, cc, jnp.zeros_like(a), *rest, **kw))
    return lambda: [setattr(ops, name, fn) for name, fn in real.items()]


def _prefill_state_at_the_padded_length():
    """FAULT: the prefill is not told which rows of a padded prompt are
    real, so the state (and the convolution window) it hands the pool
    stand at ``s_pad``, after the padding; the tick is sound."""
    from chainermn_tpu.parallel import mamba

    real = mamba.mamba_project
    mamba.mamba_project = lambda cfg, h, a, window, live, eps: real(
        cfg, h, a, window, None if h.shape[1] > 1 else live, eps)
    return lambda: setattr(mamba, "mamba_project", real)


#: broken-state programs that ``correct`` must tell from the sound one
#: (``benchmark/state_control.py``), as the Kimi Linear family names its own:
#: a logit check can be blind to a state
STATE_FAULTS = {"tick_without_decay": _tick_without_decay,
                "prefill_state_at_s_pad": _prefill_state_at_the_padded_length}


def _mesh(ctx):
    import chainermn_tpu as mn

    return mn.make_nd_mesh(("model",), (1,), ctx.devices[:1])


def _weights(ctx, mesh):
    """bfloat16 weights made on the device from the seed, each leaf placed
    where the engine wants it (so the engine's own placement copies
    nothing: 6 GB of weights are not held twice)."""
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
        # fails here, before drawing 6 GB of weights
        arch = arch_of(cfg)
        mesh = _mesh(ctx)
        params = _weights(ctx, mesh)
        self.eng = ServingEngine(
            params, head_dim=ref.sizes(cfg)["head"], mesh=mesh, arch=arch,
            **eng)
        self._weights = params      # the leaves made here: freed in close()
        pool = self.eng.pool
        self.info = {"engine": eng, "prefix_cache": True,
                     "cache_bytes_per_token": pool.bytes_per_token,
                     "cache_state_bytes_per_slot": pool.state_bytes_per_slot,
                     "pool_bytes": pool.n_slots * (
                         pool.max_total * pool.bytes_per_token
                         + pool.state_bytes_per_slot)}

    def step(self):
        # the program bounds a free slot's position itself since PR 27, so
        # the GPT-2 family's workaround is not inherited
        self.eng.step()

    def close(self):
        """Retire the engine and free its device memory NOW: the reference
        draws the same 6 GB of weights again, and an engine waiting for the
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
    them, one whose prompt is past each length of ``PAST_BUCKETS`` (fewer
    only if the window finished none such) — each as (prompt + emitted
    tokens, prompt length)."""
    done = [i for i, r in enumerate(recs) if r["handle"] is not None
            and r["handle"].status == "done"]
    if not done:
        return []
    prompt = lambda i: len(reqs[i]["prompt"])
    length = lambda i: prompt(i) + len(recs[i]["handle"].tokens)
    rng = np.random.default_rng(ctx.seed)
    order = [int(i) for i in rng.permutation(done)]
    picked = [max(done, key=length)]
    for past in PAST_BUCKETS:
        picked += [i for i in order if prompt(i) > past
                   and i not in picked][:1]
    picked += [i for i in order if i not in picked][: k - len(picked)]
    return [(np.concatenate([reqs[i]["prompt"], np.asarray(
        recs[i]["handle"].tokens, np.int32)]), prompt(i)) for i in picked]


def serve_compare(ctx, sample, precision=None):
    """The reference's one full forward over each sampled prompt with its
    served tokens (after the engine is freed), its logits read two ways
    (``reference/jamba.py::LIMITS`` says why): the mean gap by which a
    served token's logit lies below the reference's best, and the share of
    served tokens that are not the reference's first."""
    cfg = ctx.config
    lim = ref.LIMITS
    if not sample:
        return [_checks.row("served_logit_gap", float("nan"),
                            lim["served_logit_gap"])]
    # one width for every row (one compile), no wider than the longest
    # served sequence needs: the recurrence walks every position
    width = -(-max(len(seq) for seq, _ in sample) // 256) * 256 + 1
    tokens = np.zeros((len(sample), width), np.int32)
    for i, (seq, _) in enumerate(sample):
        tokens[i, : len(seq)] = seq
    params = _weights(ctx, _mesh(ctx))
    with jax.default_matmul_precision("highest"):
        got = ref.served_gaps(
            params, cfg, tokens, [p for _, p in sample],
            [len(s) for s, _ in sample], precision=precision)
    past = [sum(p > n for _, p in sample) for n in PAST_BUCKETS]
    ctx.say(f"reference: {len(sample)} served requests ({past[1]} with a "
            f"prompt past {PAST_BUCKETS[1]} tokens, {past[0]} past "
            f"{PAST_BUCKETS[0]}), {got['n']} served tokens, exact argmax "
            f"agreement {got['agree']:.4f}, gap mean {got['gap_mean']:.4g}, "
            f"widest {got['gap_max']:.4g}")
    return [_checks.row("served_logit_gap", got["gap_mean"],
                        lim["served_logit_gap"]),
            _checks.row("argmax_disagreement", 1.0 - got["agree"],
                        lim["argmax_disagreement"])]
