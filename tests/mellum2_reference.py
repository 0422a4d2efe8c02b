"""Plain Mellum 2 (JetBrains, ``model_type`` ``mellum``; the layer equations
as ISSUE 38 of this repository states them from the published
``config.json``), cut to ONE CHIP'S SHARE of a 4-chip expert-parallel
deployment, for TRAINING: the forward pass, the token NLL over the sliced
vocabulary, its gradients (``jax.grad`` of this forward) and AdamW by hand,
all in float32 ``jax.numpy`` with matmuls at ``highest`` precision.
Attention is an explicit masked softmax with the band written out (``0 <= t
- s < sliding_window``), a block of query rows at a time so that 8192 x 8192
scores fit; the held experts are a plain loop.  No kernel, no cache, no
flash, no fused loss.  Imports nothing of the program and takes nothing the
program made: weights come from :func:`init_params` (a pure function of the
seed), tokens from the driver.

Per layer ``h = x + Attn(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``; final
RMSNorm; untied head; no embedding scale; no bias anywhere.

* Attention (32 query heads, 4 KV heads of 128): ``q = W_q u``, ``[k_h |
  v_h]_h = W_kv u``; rotary in half-split pairs over all 128 columns, by
  layer kind (``rope_parameters``): a ``sliding_attention`` layer plain,
  theta 500000; a ``full_attention`` layer theta 500000 with YaRN (the blend
  of ``theta_i`` and ``theta_i / factor`` by the linear ramp between the
  dimensions that turn ``beta_fast`` and ``beta_slow`` times over the
  original length) and cos and sin times ``attention_factor``; scores ``q_t .
  k_s / sqrt(128)``; a full layer sees ``s <= t``, a sliding layer ``0 <= t -
  s < sliding_window``; softmax; query head ``h`` on KV head ``h // 8``;
  ``y_t = W_o [o_{t,h}]_h``.
* Experts (every layer is ``sparse``): ``p = softmax(W_r u)`` in float32
  over all ``num_experts``; the ``num_experts_per_tok`` largest; gates ``p``
  at the chosen, divided by their sum (``norm_topk_prob``); ``E(u) =
  W_down(silu(W_gate u) * W_up u)``; ``FFN(u) = sum_{i chosen and held
  here} g_i E_i(u)``.  No shared expert, no selection bias, no groups.

Departures from the published model, each stated in the configuration's
``reduced`` / ``assumed``:

* THE SHARE.  Of ``num_experts`` this chip holds ``num_experts_held``, the
  first ones (rank 0); of the vocabulary its first ``vocab_size`` rows.  The
  router scores all experts and the gates are normalised over all chosen,
  held here or not; what absent experts would add is LEFT OUT, in program
  and reference alike, and that partial result goes on to the next layer.
  The NLL is over the sliced vocabulary.  :func:`moe_routed` takes ``held =
  (first, n)`` so that a test can add up all the shares.
* no MTP head (the catalog's ``described_as`` names one; ``config.json`` has
  no key for it) and no QK-norm (``config.json`` names none);
* no auxiliary balance term in the loss: ``config.json`` gives no recipe and
  none is invented; the router learns through its gates.

``precision`` selects how every matmul's operands are rounded: ``float32``
(the reference), ``bfloat16`` (what the configuration states), ``fp8`` (the
control: e4m3 with one scale per tensor, the nearest precision below bf16);
rounding is straight through for the gradient.  Recomputation (a layer, a
block of queries, an expert at a time) changes memory, not mathematics.
"""

import math
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST

# ---- limits of the comparison that decides ``correct`` --------------------
# Each is set between two readings on the chip at the cell's own size (my
# chip runs, PR 38; PERF.md, section 2, has every reading with its seed):
# the largest that sound runs of the program gave, and the smallest that a
# run which has to fail gave — the fp8 control (``benchmark/control.py``:
# this reference put in the program's place at e4m3) and the faults planted
# in the trainer through ``run.py`` (``benchmark/tests/plant_fault.py``: a
# state returned unchanged, a step on half its batch).  The program's
# routes are the timed step's own output.  The routes tell the precisions
# apart; the norms are held against a gross fault; the loss is left out.
LIMITS = {
    # the share of (token, layer) pairs of the FIRST step's batch (2 x 8192
    # x 4) whose chosen experts differ from the reference's, at the seeded
    # weights.  Top-k is discontinuous: a token whose 8th and 9th
    # probabilities tie within bfloat16's rounding of the layer's input
    # routes differently, and every flip moves the input of the layers
    # above.  Sound runs 0.0565 .. 0.0604; the fp8 control 0.3690, 0.3699;
    # half a batch about a half
    "route_disagreement": 0.15,
    # the same share of the LAST check step's batch, at the weights that
    # the steps before it left: two backward passes and two AdamW updates
    # moved the router and everything below it, so a state that did not
    # move, or moved wrongly, routes differently.  Between the sound runs'
    # largest and the control's and the frozen state's smallest (PERF.md,
    # section 2)
    "route_disagreement_updated": 0.2,
    # worst leaf, the first gradient as AdamW got it (from its first
    # moment): sound runs 3.8e-4 .. 1.62e-3; the fp8 control 1.1e-3, 3.15e-3
    # — its rounding is straight through, so its backward is exact and the
    # precision hardly moves this number (a flipped route moves one token's
    # share of a 16384-token gradient).  Held against a gross fault: half a
    # batch and a frozen state read tenths and more (PERF.md, section 2)
    "grad_norm_gap": 1e-2,
    # worst leaf, the parameters' change after the three steps: sound runs
    # 6.3e-5 .. 1.3e-4, the fp8 control 1.9e-4, 3.2e-4 — AdamW's first steps
    # are the gradient's sign times the rate, which precision hardly moves.
    # Held against a step that returns its state unchanged (1.0), with the
    # more room above the sound reading since fresh seeds read higher.
    "update_norm_gap": 1e-2,
    # NO ``loss_gap``: |loss - reference loss| over the three steps read
    # 8.8e-5 .. 3.9e-4 in the sound runs and 3.5e-4, 1.7e-3 in the control —
    # the precision hardly moves it, and the accepted training cell's limit
    # (7e-4) leaves the first sound reading (2.8e-4) under three times of
    # room, so the cell's comparison leaves the loss out (both lists of
    # losses are printed as free lines; a non-finite loss still fails).
}

#: queries per block of the explicit softmax: ``(kv heads, group, block,
#: keys)`` float32 scores, 0.5 GB at 32 heads and 8192 keys
QUERY_BLOCK = 512


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
    return jnp.einsum(spec, _round(a.astype(jnp.float32), precision),
                      _round(b.astype(jnp.float32), precision),
                      precision=HIGHEST, preferred_element_type=jnp.float32)


def sizes(cfg) -> dict:
    """The numbers the forward needs, from the configuration's keys."""
    return {
        "d": cfg["hidden_size"], "layers": cfg["num_hidden_layers"],
        "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"], "head_dim": cfg["head_dim"],
        "window": cfg["sliding_window"],
        "sliding": tuple(t == "sliding_attention"
                         for t in cfg["layer_types"]),
        "expert_inner": cfg["moe_intermediate_size"],
        "experts": cfg["num_experts"],
        "held": cfg.get("num_experts_held", cfg["num_experts"]),
        "top_k": cfg["num_experts_per_tok"],
        "renorm": bool(cfg["norm_topk_prob"]),
        "vocab": cfg["vocab_size"], "eps": cfg["rms_norm_eps"],
    }


def init_params(key, cfg, dtype=jnp.float32):
    """Seeded weights in the layout the program's LM takes (the
    configuration's ``assumed.init``).  Projections are ``normal(0, 1 /
    sqrt(fan_in))`` so that every activation stays of order one through the
    depth — the router's too, whose logits are then of order one and its
    softmax neither flat nor one-hot — except the queries', which are
    ``assumed.init``'s ``query_gain`` times that: with unit-variance scores
    over a thousand to eight thousand keys the softmax is an average, every
    context is near zero, and neither a window nor a gradient through the
    attention would show.  Norms 1, embedding ``normal(0, 1)``, head
    ``normal(0, 1 / sqrt(d))``."""
    z = sizes(cfg)
    d, hd, kv, h = z["d"], z["head_dim"], z["kv_heads"], z["heads"]
    gain = cfg["assumed"]["init"]["query_gain"]

    def draw(k, shape, s):
        return (jax.random.normal(k, shape, jnp.float32) * s).astype(dtype)

    def dense(k, n_in, n_out, lead=()):
        return draw(k, lead + (n_in, n_out), n_in ** -0.5)

    def block(k):
        k1, k2, k3, k4, k5, k6, k7 = jax.random.split(k, 7)
        held, inner = z["held"], z["expert_inner"]
        return {"ln1_scale": jnp.ones((d,), dtype),
                "ln2_scale": jnp.ones((d,), dtype),
                "attn": {"wq": draw(k1, (d, h * hd), gain * d ** -0.5),
                         # per KV head [k_h | v_h]
                         "wkv": dense(k2, d, 2 * kv * hd),
                         "wo": dense(k3, h * hd, d)},
                "moe": {"router": dense(k4, d, z["experts"]),
                        "w_gate": dense(k5, d, inner, (held,)),
                        "w_up": dense(k6, d, inner, (held,)),
                        "w_down": dense(k7, inner, d, (held,))}}

    keys = jax.random.split(key, z["layers"] + 2)
    return {
        "embed": draw(keys[0], (z["vocab"], d), 1.0),
        "head": draw(keys[1], (z["vocab"], d), d ** -0.5),
        "lnf_scale": jnp.ones((d,), dtype),
        "blocks": [block(keys[2 + i]) for i in range(z["layers"])],
    }


# ---- the layers ------------------------------------------------------------

def rms_norm(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def rotary_tables(cfg, sliding: bool, n_positions: int):
    """``(cos, sin (S, head_dim / 2))`` of a layer kind, written out from
    ``rope_parameters``: every column of a head turns."""
    rp = cfg["rope_parameters"][
        "sliding_attention" if sliding else "full_attention"]
    rot = cfg["head_dim"]
    theta = float(rp["rope_theta"])
    inv = theta ** -(np.arange(0, rot, 2, dtype=np.float64) / rot)
    scale = 1.0
    if rp["rope_type"] == "yarn":
        orig = rp["original_max_position_embeddings"]

        def turns_to_dim(turns):
            return rot * math.log(orig / (turns * 2 * math.pi)) / (
                2 * math.log(theta))

        low = max(math.floor(turns_to_dim(rp["beta_fast"])), 0)
        high = min(math.ceil(turns_to_dim(rp["beta_slow"])), rot - 1)
        ramp = np.clip((np.arange(rot // 2) - low)
                       / ((high - low) or 0.001), 0.0, 1.0)
        # ramp 0: the frequency as it is; 1: divided by the factor
        inv = inv * (1.0 - ramp) + inv / rp["factor"] * ramp
        scale = rp["attention_factor"]
    ang = np.arange(n_positions, dtype=np.float64)[:, None] * inv[None, :]
    return (jnp.asarray(np.cos(ang) * scale, jnp.float32),
            jnp.asarray(np.sin(ang) * scale, jnp.float32))


def rotate(x, cos, sin):
    """``x (B, S, H, d)``: half-split pairs ``(i, i + d / 2)`` turned."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def attention(u, a, cfg, layer, precision, window=None):
    """The attention of layer ``layer`` on normed ``u (B, S, D)``.
    ``window``: the band the layer keeps (None: what the configuration says
    of the layer) — a test says otherwise."""
    z = sizes(cfg)
    b, s, _ = u.shape
    h, kv, hd = z["heads"], z["kv_heads"], z["head_dim"]
    sliding = z["sliding"][layer]
    if window is None:
        window = z["window"] if sliding else None
    q = _mm("bsd,df->bsf", u, a["wq"], precision).reshape(b, s, h, hd)
    kvp = _mm("bsd,df->bsf", u, a["wkv"], precision).reshape(b, s, kv, 2, hd)
    cos, sin = rotary_tables(cfg, sliding, s)
    q = rotate(q, cos, sin).reshape(b, s, kv, h // kv, hd)
    k, v = rotate(kvp[..., 0, :], cos, sin), kvp[..., 1, :]
    keys = jnp.arange(s)

    @jax.checkpoint
    def block(qb, t):                 # a block of queries against all keys
        scores = _mm("bqhgd,bkhd->bhgqk", qb, k, precision) / math.sqrt(hd)
        dist = t[:, None] - keys[None, :]
        seen = dist >= 0
        if window is not None:
            seen &= dist < window
        p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return _mm("bhgqk,bkhd->bqhgd", p, v, precision)

    qb = min(QUERY_BLOCK, s)
    if s % qb:
        raise ValueError(f"{s} positions are no whole blocks of {qb}")
    split = lambda t: jnp.moveaxis(
        t.reshape((b, s // qb, qb) + t.shape[2:]), 1, 0)
    ctx = jax.lax.map(lambda args: block(*args),
                      (split(q), keys.reshape(s // qb, qb)))
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(b, s, h * hd)
    return _mm("bsf,fd->bsd", ctx, a["wo"], precision)


def gated_mlp(u, p, precision):
    g = _mm("...d,df->...f", u, p["w_gate"], precision)
    up = _mm("...d,df->...f", u, p["w_up"], precision)
    return _mm("...f,fd->...d", jax.nn.silu(g) * up, p["w_down"], precision)


def route(u, m, cfg, precision):
    """``(idx (..., k), gates (..., k))`` over all routed experts: softmax
    in float32, plain top-k, renormalised where ``norm_topk_prob``."""
    z = sizes(cfg)
    p = jax.nn.softmax(_mm("...d,de->...e", u, m["router"], precision), -1)
    gates, idx = jax.lax.top_k(p, z["top_k"])
    if z["renorm"]:
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
    return idx, gates


def moe_routed(u, m, idx, gates, held, precision):
    """``sum_{i chosen and held} g_i E_i(u)``: a plain loop (a scan, one
    expert at a time, each recomputed in the backward pass) over the experts
    ``[first, first + n)`` that ``m['w_*']`` stack."""
    first, n = held

    @jax.checkpoint
    def one(out, xs):
        j, e = xs
        g = jnp.where(idx == first + j, gates, 0.0).sum(-1)
        return out + gated_mlp(u, e, precision) * g[..., None], None

    stacked = {name: m[name][:n] for name in ("w_gate", "w_up", "w_down")}
    out, _ = jax.lax.scan(one, jnp.zeros(u.shape, jnp.float32),
                          (jnp.arange(n), stacked))
    return out


def layer(x, blk, cfg, index: int, precision="float32", held=None):
    """One layer: ``(y, idx)``, the chosen experts sorted ascending within
    a token."""
    z = sizes(cfg)
    held = (0, z["held"]) if held is None else held
    u = rms_norm(x, blk["ln1_scale"], z["eps"])
    x = x + attention(u, blk["attn"], cfg, index, precision)
    u = rms_norm(x, blk["ln2_scale"], z["eps"])
    idx, gates = route(u, blk["moe"], cfg, precision)
    y = moe_routed(u, blk["moe"], idx, gates, held, precision)
    return x + y, jnp.sort(idx, -1)


def hidden(params, cfg, tokens, precision="float32"):
    """``(x (B, S, D) before the final norm, routes (B, S, L, k))`` of
    ``tokens (B, S)``; each layer recomputed in the backward pass."""
    x = params["embed"].astype(jnp.float32)[tokens]
    routes = []
    for i, blk in enumerate(params["blocks"]):
        x, r = jax.checkpoint(partial(layer, cfg=cfg, index=i,
                                      precision=precision))(x, blk)
        routes.append(r)
    return x, jnp.stack(routes, axis=2)


def loss_sum(params, tokens, cfg, precision="float32"):
    """``(summed next-token NLL over the sliced vocabulary, routes)`` of
    ``tokens (B, S+1)``, a row at a time."""
    z = sizes(cfg)

    @jax.checkpoint
    def row(tok):
        x, routes = hidden(params, cfg, tok[None, :-1], precision)
        h = rms_norm(x, params["lnf_scale"], z["eps"])
        lg = _mm("bsd,vd->bsv", h, params["head"], precision)
        logp = jax.nn.log_softmax(lg, axis=-1)
        nll = -jnp.take_along_axis(logp, tok[None, 1:, None], axis=-1).sum()
        return nll, routes[0]

    nll, routes = jax.lax.map(row, tokens)
    return nll.sum(), routes


def leaf_norms(tree):
    """L2 norm of every leaf, as one vector in ``tree_leaves`` order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree_util.tree_leaves(tree)])


def worst_leaf_gap(got, want):
    """The gap between the program's norm and the reference's, by the worst
    leaf, against the reference's norm of that leaf or of the median leaf,
    whichever is larger."""
    floor = jnp.median(want)
    return float(jnp.max(jnp.abs(got - want) / jnp.maximum(want, floor)))


def route_disagreement(got, want) -> float:
    """Share of (token, layer) pairs whose chosen experts differ: ``got``,
    ``want (B, S, L, k)``, each token's experts in any order."""
    got, want = (np.sort(np.asarray(r), -1) for r in (got, want))
    return float((got != want).any(-1).mean())


def train_steps(key, cfg, opt, batches, *, precision="float32"):
    """The first ``len(batches)`` AdamW steps from seeded weights.

    Returns each step's mean loss, the per-leaf norms of the first gradient
    and of the parameters' change after all the steps, and each step's
    routes ``(B, S, L, k)``: the first at the seeded weights, the later
    ones at the weights the steps before them left."""
    b1, b2, lr, wd = opt["b1"], opt["b2"], opt["lr"], opt["weight_decay"]
    adam_eps = opt.get("eps", 1e-8)
    with jax.default_matmul_precision("highest"):
        init = jax.jit(partial(init_params, cfg=cfg))
        grad = jax.jit(jax.value_and_grad(partial(
            loss_sum, cfg=cfg, precision=precision), has_aux=True))

        @partial(jax.jit, donate_argnums=(0, 1, 2, 3))
        def adamw(p, m, v, g, t, n_tok):
            def one(p, m, v, g):
                g = g / n_tok
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
        # AdamW's moments wait on the HOST between steps: beside them the
        # gradient's program (2.4 GB of parameters in, 2.4 GB of gradients
        # out, 5.4 GB of temporaries at the published widths) would not
        # leave the chip's 16 GB a safe margin
        m = jax.tree_util.tree_map(lambda a: np.zeros(a.shape, a.dtype), p)
        v = jax.tree_util.tree_map(lambda a: np.zeros(a.shape, a.dtype), p)
        losses, grad_norms, routes, step_s = [], None, [], []
        for t, tokens in enumerate(batches, start=1):
            t0 = time.perf_counter()
            n_tok = tokens.shape[0] * (tokens.shape[1] - 1)
            (total, r), grads = grad(p, tokens)
            losses.append(float(total / n_tok))
            if grad_norms is None:
                grad_norms = jax.device_get(
                    jax.jit(leaf_norms)(grads)) / n_tok
            routes.append(np.asarray(r))
            del r
            p, m, v = adamw(p, jax.device_put(m), jax.device_put(v), grads,
                            jnp.float32(t), jnp.float32(n_tok))
            del grads
            m, v = jax.device_get((m, v))
            step_s.append(time.perf_counter() - t0)
        update_norms = jax.jit(lambda p, k: leaf_norms(
            jax.tree_util.tree_map(jnp.subtract, p, init(k))))(p, key)
        out = {"losses": losses, "step_s": step_s,
               "grad_norms": grad_norms, "routes": routes,
               "update_norms": jax.device_get(update_norms)}
    del p, m, v
    return out
