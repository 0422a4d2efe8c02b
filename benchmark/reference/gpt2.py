"""Plain GPT-2 (Radford et al. 2019): float32 ``jax.numpy``, matmuls at
``highest`` precision, dense causal attention, plain log-softmax, AdamW by
hand.  No kernels, no cache, no batching tricks.  Imports nothing of the
program and takes nothing the program made: weights come from
:func:`init_params` (a pure function of the seed), tokens from the driver.

Departures from the published model, each because the program under test
(``chainermn_tpu/parallel/transformer.py``) computes it so and the check has
to follow the same mathematics:

* the token embedding is multiplied by ``sqrt(n_embd)`` before the position
  embedding is added (GPT-2 does not scale it);
* the embedding table has ``padded_vocab`` rows (the configuration's
  ``assumed``: Megatron's divisible-by-128 padding, 50257 -> 50304), all
  random, all in the softmax; tokens are drawn below ``vocab_size``;
* ``c_attn``'s columns are laid out head-major, ``[head: q | k | v]``.

``precision`` selects how every matmul's operands are rounded: ``float32``
(the reference), ``bfloat16`` (what the configuration states), ``fp8`` (the
control: e4m3 with one scale per tensor, the nearest precision below bf16).
Block-wise recomputation (``jax.checkpoint`` per layer, rows in blocks)
changes memory, not mathematics.
"""

import time
from functools import partial

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST

# ---- limits of the comparison that decides ``correct`` --------------------
# Each was set from two readings on the chip at the cell's own size (my chip
# runs, PR 24; PERF.md section 2 repeats them): the largest that sound runs of
# the program gave over a dozen seeds or more, and the smallest that the fp8
# control gave (``benchmark/control.py``).
LIMITS = {
    # training (benchmark/drivers/train_steps.py), three steps from the seed
    # |loss - reference loss|, each step.  Sound runs 1.4e-5 .. 2.2e-4; fp8
    # control 3.6e-4 .. 1.1e-3 (it need not fail this one).  The loss at
    # seeded weights hardly moves with precision: it is held against a part
    # of the batch left out (one row of eight shifts the mean by about
    # 1e-3), at three times the sound largest.
    "loss_gap": 7e-4,
    # worst leaf, the first gradient as AdamW got it (from its first moment).
    # Sound runs 1.2e-3 .. 3.4e-3 over 16 seeds; fp8 control 1.2e-2 ..
    # 2.7e-2 (straight-through rounding, so that its backward is exact).
    "grad_norm_gap": 8e-3,
    # worst leaf, the parameters' change after the three steps.  Sound runs
    # 0.175 .. 0.187, always the same leaves: Adam divides by a second moment
    # that is all rounding where a gradient is zero by construction (the key
    # bias).  Held against a step that returns its state unchanged (gap 1.0),
    # at about three times the sound largest.
    "update_norm_gap": 0.5,
    # serving (benchmark/drivers/serve_open_loop.py): the widest gap by which
    # a served token's float32 logit lies below the float32 best, over 8
    # served requests (750-1250 tokens).  Sound runs 0 .. 1.2e-2 over 14
    # seeds at 3.6 requests/s (nine of them exactly 0: a widest gap swings by
    # its nature) and 0 .. 8.0e-3 over 17 more at 33.6/s; fp8 control 5.4e-2
    # .. 1.5e-1 over 6 seeds, and 3.96e-2, 1.27e-1, 1.31e-1 over three more
    # (PR 46: the first PASSED the 4e-2 this limit was, so it came down;
    # 2.5 x over the sound largest, 1.3 x under the control's smallest).
    "served_logit_gap": 3e-2,
}


def _round(x, precision):
    """``x`` rounded to ``precision`` and back, straight through for the
    gradient (a cast's own backward would round the cotangent too, and
    float8 flushes every gradient of this size to zero)."""
    if precision == "float32":
        return x
    if precision == "bfloat16":
        q = x.astype(jnp.bfloat16).astype(x.dtype)
    elif precision == "fp8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
        q = (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale
    else:
        raise ValueError(f"unknown precision {precision!r}")
    return x + jax.lax.stop_gradient(q - x)


def _mm(spec, a, b, precision):
    return jnp.einsum(spec, _round(a, precision), _round(b, precision),
                      precision=HIGHEST, preferred_element_type=jnp.float32)


def init_params(key, cfg, dtype=jnp.float32):
    """Seeded weights in the layout the program's LM takes (the
    configuration's ``assumed.init``).  Block weights are He-normal,
    ``sqrt(2 / fan_in)``, as the program's own initialiser draws them, so
    that after 24 layers the blocks and not the input token set the logits:
    with GPT-2's own 0.02 and this program's ``sqrt(n_embd)`` embedding
    scale, the tied head echoes the input token by a margin no rounding can
    move, and a check on served tokens would pass in any precision (my chip
    runs, PR 24: 1,168 served tokens, every one the float32 argmax).  The
    token embedding is drawn at 0.01 for the same reason, positions at 0.3
    (so that they weigh about as much as the scaled token embedding)."""
    d, n_layer, inner = cfg["n_embd"], cfg["n_layer"], cfg["n_inner"]
    vocab = cfg.get("assumed", {}).get("padded_vocab", cfg["vocab_size"])
    keys = jax.random.split(key, 2 + 4 * n_layer)

    def normal(k, shape, s):
        return (jax.random.normal(k, shape, jnp.float32) * s).astype(dtype)

    def he(k, n_in, n_out):
        return normal(k, (n_in, n_out), (2.0 / n_in) ** 0.5)

    def block(i):
        k1, k2, k3, k4 = keys[2 + 4 * i: 6 + 4 * i]
        return {
            "ln1_scale": jnp.ones((d,), dtype), "ln1_bias": jnp.zeros((d,), dtype),
            "ln2_scale": jnp.ones((d,), dtype), "ln2_bias": jnp.zeros((d,), dtype),
            "attn": {"wqkv": he(k1, d, 3 * d), "bqkv": jnp.zeros((3 * d,), dtype),
                     "wo": he(k2, d, d), "bo": jnp.zeros((d,), dtype)},
            "mlp": {"wi": he(k3, d, inner), "bi": jnp.zeros((inner,), dtype),
                    "wo": he(k4, inner, d), "bo": jnp.zeros((d,), dtype)},
        }

    return {
        "embed": normal(keys[0], (vocab, d), 0.01),
        "pos_embed": normal(keys[1], (cfg["n_positions"], d), 0.3),
        "blocks": [block(i) for i in range(n_layer)],
        "lnf_scale": jnp.ones((d,), dtype), "lnf_bias": jnp.zeros((d,), dtype),
    }


def _layer_norm(x, scale, bias, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        (2.0 / jnp.pi) ** 0.5 * (x + 0.044715 * x ** 3)))


def _block(x, p, *, n_head, eps, precision):
    b, s, d = x.shape
    hd = d // n_head
    h = _layer_norm(x, p["ln1_scale"], p["ln1_bias"], eps)
    qkv = _mm("bsd,de->bse", h, p["attn"]["wqkv"], precision) + p["attn"]["bqkv"]
    qkv = qkv.reshape(b, s, n_head, 3, hd)
    q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
    scores = _mm("bqhd,bkhd->bhqk", q, k, precision) / hd ** 0.5
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = _mm("bhqk,bkhd->bqhd", probs, v, precision).reshape(b, s, d)
    x = x + _mm("bsd,de->bse", ctx, p["attn"]["wo"], precision) + p["attn"]["bo"]
    h = _layer_norm(x, p["ln2_scale"], p["ln2_bias"], eps)
    h = _gelu_new(_mm("bsd,df->bsf", h, p["mlp"]["wi"], precision) + p["mlp"]["bi"])
    return x + _mm("bsf,fd->bsd", h, p["mlp"]["wo"], precision) + p["mlp"]["bo"]


def hidden(params, tokens, *, n_head, eps=1e-5, precision="float32",
           remat=False):
    """Final-LayerNorm hidden states ``(B, S, D)`` of ``tokens (B, S)``.
    The layers run under ``lax.scan`` over their stacked weights: one block
    for the compiler instead of ``n_layer`` copies, the same numbers."""
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    d = params["embed"].shape[1]
    x = params["embed"][tokens] * d ** 0.5 + params["pos_embed"][: tokens.shape[1]]
    blk = partial(_block, n_head=n_head, eps=eps, precision=precision)
    if remat:
        blk = jax.checkpoint(blk)
    stacked = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *params["blocks"])
    x, _ = jax.lax.scan(lambda x, p: (blk(x, p), None), x, stacked)
    return _layer_norm(x, params["lnf_scale"], params["lnf_bias"], eps)


def logits(params, tokens, *, n_head, eps=1e-5, precision="float32"):
    """``(B, S, V)`` float32 logits through the tied head."""
    h = hidden(params, tokens, n_head=n_head, eps=eps, precision=precision)
    return _mm("bsd,vd->bsv", h, params["embed"].astype(jnp.float32), precision)


def loss_sum(params, tokens, *, n_head, eps=1e-5, precision="float32"):
    """Summed next-token NLL of ``tokens (B, S+1)``."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    h = hidden(params, inputs, n_head=n_head, eps=eps, precision=precision,
               remat=True)
    lg = _mm("bsd,vd->bsv", h, params["embed"], precision)
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1).sum()


def leaf_norms(tree):
    """L2 norm of every leaf, as one vector in ``tree_leaves`` order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree_util.tree_leaves(tree)])


def worst_leaf_gap(got, want):
    """The gap between the program's norm and the reference's, by the worst
    leaf, against the reference's norm of that leaf or of the median leaf,
    whichever is larger (some gradients are all but zero)."""
    floor = jnp.median(want)
    return float(jnp.max(jnp.abs(got - want) / jnp.maximum(want, floor)))


def train_steps(key, cfg, opt, batches, *, rows_per_block=2,
                precision="float32"):
    """The first ``len(batches)`` AdamW steps from seeded weights.

    Returns each step's mean loss, the per-leaf norms of the first gradient
    and of the parameters' change after all the steps."""
    n_head, eps = cfg["n_head"], cfg["layer_norm_epsilon"]
    b1, b2, lr, wd = opt["b1"], opt["b2"], opt["lr"], opt["weight_decay"]
    adam_eps = opt.get("eps", 1e-8)
    with jax.default_matmul_precision("highest"):
        init = jax.jit(partial(init_params, cfg=cfg))
        block_grad = jax.jit(jax.value_and_grad(partial(
            loss_sum, n_head=n_head, eps=eps, precision=precision)))

        @partial(jax.jit, donate_argnums=(0,))
        def accumulate(acc, g):
            return jax.tree_util.tree_map(jnp.add, acc, g)

        @partial(jax.jit, donate_argnums=(0, 1, 2))
        def adamw(p, m, v, g, t):
            def one(p, m, v, g):
                m = b1 * m + (1 - b1) * g
                v = b2 * v + (1 - b2) * g * g
                step = (m / (1 - b1 ** t)) / (
                    jnp.sqrt(v / (1 - b2 ** t)) + adam_eps)
                return p - lr * (step + wd * p), m, v
            out = jax.tree_util.tree_map(one, p, m, v, g)
            pick = lambda i: jax.tree_util.tree_map(
                lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
            return pick(0), pick(1), pick(2)

        p = init(key)
        m = jax.tree_util.tree_map(jnp.zeros_like, p)
        v = jax.tree_util.tree_map(jnp.zeros_like, p)
        losses, grad_norms, step_s = [], None, []
        for t, tokens in enumerate(batches, start=1):
            t0 = time.perf_counter()
            n_tok = tokens.shape[0] * (tokens.shape[1] - 1)
            total, grads = 0.0, None
            for r in range(0, tokens.shape[0], rows_per_block):
                l, g = block_grad(p, tokens[r: r + rows_per_block])
                total = total + l
                grads = g if grads is None else accumulate(grads, g)
                del g
            grads = jax.tree_util.tree_map(lambda x: x / n_tok, grads)
            losses.append(float(total / n_tok))
            if grad_norms is None:
                grad_norms = leaf_norms(grads)
            p, m, v = adamw(p, m, v, grads, jnp.float32(t))
            del grads
            step_s.append(time.perf_counter() - t0)
        update_norms = jax.jit(lambda p, k: leaf_norms(
            jax.tree_util.tree_map(jnp.subtract, p, init(k))))(p, key)
        out = {"losses": losses, "step_s": step_s, "grad_norms": jax.device_get(grad_norms),
               "update_norms": jax.device_get(update_norms)}
    del p, m, v
    return out


def served_gaps(params, cfg, tokens, prompt_lens, total_lens, *,
                precision=None, rows_per_block=4):
    """Over the generated positions of each served sequence: the widest gap
    by which the emitted token's float32 logit lies below the float32 best,
    and the share of exact argmax agreement.

    ``tokens (N, L)``: prompt then emitted tokens, padded to one length (the
    attention is causal, so padding behind a sequence changes nothing before
    it); ``prompt_lens``, ``total_lens (N,)``.  With ``precision`` set (the
    control), the token judged at each position is the one that precision
    puts first on the same prefix, not the emitted one."""
    n_head, eps = cfg["n_head"], cfg["layer_norm_epsilon"]

    @jax.jit
    def block(params, tok, plen, tlen):
        ref = logits(params, tok[:, :-1], n_head=n_head, eps=eps)
        if precision is None:
            chosen = tok[:, 1:]
        else:
            chosen = jnp.argmax(logits(params, tok[:, :-1], n_head=n_head,
                                       eps=eps, precision=precision), axis=-1)
        picked = jnp.take_along_axis(ref, chosen[..., None], axis=-1)[..., 0]
        pos = jnp.arange(tok.shape[1] - 1)[None, :]   # logits at pos -> pos+1
        live = (pos >= plen[:, None] - 1) & (pos < tlen[:, None] - 1)
        vocab = ref.shape[-1]
        gap = ref.max(-1) - picked
        # a token outside the table (the engine's no-winner sentinel when a
        # row went NaN) or a NaN logit is as wrong as a token can be
        gap = jnp.where((chosen < 0) | (chosen >= vocab) | jnp.isnan(gap),
                        jnp.inf, gap)
        gap = jnp.where(live, gap, 0.0)
        same = live & (chosen == jnp.argmax(ref, axis=-1))
        return gap.max(), same.sum(), live.sum()

    worst, agree, n = 0.0, 0, 0
    tokens = jnp.asarray(tokens, jnp.int32)
    prompt_lens = jnp.asarray(prompt_lens, jnp.int32)
    total_lens = jnp.asarray(total_lens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        for r in range(0, tokens.shape[0], rows_per_block):
            sl = slice(r, r + rows_per_block)
            g, a, m = block(params, tokens[sl], prompt_lens[sl],
                            total_lens[sl])
            worst, agree, n = max(worst, float(g)), agree + int(a), n + int(m)
    return worst, agree / max(n, 1)
