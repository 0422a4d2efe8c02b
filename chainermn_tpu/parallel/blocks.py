"""The LM block's vocabulary, described once and used by every path.

``parallel/transformer.py`` (the training loss) and ``parallel/decode.py``
(prefill, the decode tick, the serving engine's programs) used to hard-code
the same block twice: LayerNorm, ``gelu`` MLP with biases, a head tied to
the embedding, the embedding times ``sqrt(d)``.  :class:`LMArch` names
those choices; both modules read them through the helpers below, so a
GPT-2-style model is this description with its default values and a model
with RMSNorm, SiLU-gated MLPs, an untied head, multi-head latent attention
and routed experts is the same code with other values — not a third copy
of the block.

Parameter layout per ``arch`` value (all GLOBAL arrays):

* ``norm='layernorm'``: ``ln1_scale/ln1_bias/ln2_scale/ln2_bias`` per
  block, ``lnf_scale/lnf_bias``; ``'rmsnorm'``: the ``*_scale`` only.
* ``mlp='gelu'``: ``mlp = {wi, bi, wo, bo}`` (``tensor_parallel.tp_mlp``);
  ``'swiglu'``: ``mlp = {w_gate, w_up, w_down}``, no biases.
* layer kind ``'moe'``: ``moe = {router (D, E), router_bias (E,), shared =
  {w_gate, w_up, w_down}, w_gate/w_up (E_held, D, F), w_down (E_held, F,
  D)}`` — ``parallel/moe.py::moe_dropless``; a ``'softmax'`` router has no
  ``router_bias`` and a layer with ``n_shared = 0`` no ``shared``.
* ``attn='mha'``: ``attn = {wqkv, bqkv, wo, bo}`` or the GQA form ``{wq,
  bq, wkv, bkv, wo, bo}`` (``wkv`` columns per KV head ``[k_h | v_h]``; the
  query-head count is the weights', so it may differ from layer to layer);
  ``attn_bias=False``: a model whose attention has no biases carries none
  (no ``b*`` entry, not zero vectors); ``attn_gate=True`` adds ``wg (D,
  H)``, the per-head sigmoid gate on the context; ``windows`` / ``rotary``
  give a layer its band and its rotation (below); ``'mla'``: ``attn = {wdq (D, q_rank), q_norm, wuq (q_rank,
  H·(nope+rope)), wdkv (D, kv_rank+rope), kv_norm, wukv (kv_rank,
  H·(nope+v)), wo (H·v, D)}``, head-major columns — with ``q_lora_rank =
  None`` the queries are projected directly, ``wq (D, H·(nope+rope))`` in
  place of ``wdq / q_norm / wuq``; ``'kda'`` (a gated delta-rule layer,
  ``parallel/kda.py``): ``attn = {wqkv (D, 3·H·d), conv (W, 3·H·d), w_low
  (D, 2·rank + H), wf_up / wg_up (rank, H·d), dt_bias (H·d,), a_log (H,),
  o_norm (d,), wo (H·d, D)}``; ``'mamba'`` (a Mamba-1 selective
  state-space layer, ``parallel/mamba.py``; ``E = expand·D`` inner channels,
  ``N`` states a channel, ``R`` the step's rank): ``attn = {w_in (D, 2·E)``
  columns ``[u | z]``, ``conv (W, E)`` (``conv[-1]`` meets the current
  token), ``conv_bias (E,), w_x (E, R + 2·N)`` columns ``[dt | B | C]``,
  ``dt_norm (R,), b_norm (N,), c_norm (N,), w_dt (R, E), dt_bias (E,),
  a_log (N, E)`` (the published ``A_log`` TRANSPOSED: channels on the
  lanes), ``d (E,), w_out (E, D)}``, no projection biases.
* ``attn_kinds``: the attention kind of each LAYER where a model mixes
  them (None: every layer is ``attn``), as ``layer_kinds`` is for the MLP;
  a kind is a key of :data:`LAYER_KINDS`, the ONE table of what a kind
  keeps and what runs it in the training loss and in serving.
* ``tied_head=False``: ``params['head'] (V, D)`` beside ``params['embed']``.

What a layer's attention keeps is DECLARED here (:func:`cache_layout`) and
the serving pool allocates exactly that.  ROWS, one a token: an MHA/GQA
layer a ``(k, v)`` pair of ``n_kv·head_dim`` columns sharded over the model
axis, an MLA layer ONE latent buffer ``[c_kv | RoPE(k_rope) | zero pad]``,
replicated.  RING, the last ``W`` rows of a sequence: an MHA/GQA layer with a
window (``windows[layer] = W``: a token sees itself and the ``W - 1`` before
it) keeps its ``(k, v)`` pair for those alone, position ``p`` at ring row ``p
% W``, each key rotated at its absolute position before it is cached (so the
order of the rows means nothing to the softmax).  STATE, one a sequence
whatever its length: a KDA layer its ``(H, d, d)`` float32 recurrent state
and the last ``W - 1`` rows of its fused projection; a Mamba layer its ``(N,
E)`` float32 state — stored ``(N, E / L, L)``, ``L`` lanes of channels, the
layout its kernels take (``ops/ssm_step.py``) — and the last ``W - 1`` rows
of ``u``.

``positions=False``: the model has NO positional signal — no position
table and no rotation (its state layers carry the order); :func:`turn_qk`
then hands q and k back as they are.
"""

from __future__ import annotations

import math
from contextlib import nullcontext
from dataclasses import dataclass
from importlib import import_module
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

_LANES = 128


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention: the numbers the shapes do not give."""
    n_heads: int
    q_lora_rank: Optional[int]          # None: queries projected directly
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float = 10000.0
    #: YaRN: ``(factor, original_max_position, beta_fast, beta_slow,
    #: mscale, mscale_all_dim)`` or None for plain rotary
    yarn: Optional[Tuple[float, int, float, float, float, float]] = None
    #: False: no rotation at all (the ``rope`` columns are carried as they
    #: are projected; the row keeps its width)
    rope: bool = True

    @property
    def latent_width(self) -> int:
        """Columns of a cached row: ``kv_lora_rank + qk_rope_head_dim``,
        rounded up to whole 128-lane tiles.  On the v5e a 576-wide bf16
        buffer is not in the layout the flash-decode kernel takes and XLA
        copies the whole cache in front of every call (my ahead-of-time
        compile, PR 27); 640 columns pass through untouched."""
        w = self.kv_lora_rank + self.qk_rope_head_dim
        return -(-w // _LANES) * _LANES

    @property
    def softmax_scale(self) -> float:
        s = (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
        if self.yarn is not None:
            factor, _, _, _, _, mscale_all_dim = self.yarn
            m = _yarn_mscale(factor, mscale_all_dim)
            s = s * m * m
        return s


@dataclass(frozen=True)
class MoEConfig:
    """Routed experts: this chip's share ``held = (first, n)`` of the
    ``n_experts`` the router scores.  ``router``: ``'sigmoid_group'``
    (sigmoid scores, a selection-only bias, group-limited top-k) or
    ``'softmax'`` (softmax over all experts, plain top-k, no bias and no
    groups: ``n_group`` / ``topk_group`` are not read).  ``n_shared``: how
    many shared experts every token takes beside the routed ones (0: the
    layer has no ``shared`` parameters at all)."""
    n_experts: int
    top_k: int
    n_group: int
    topk_group: int
    routed_scaling_factor: float
    norm_topk_prob: bool = True
    held: Tuple[int, int] = (0, 0)
    router: str = "sigmoid_group"      # | 'softmax'
    n_shared: int = 1


@dataclass(frozen=True)
class KDAConfig:
    """A gated delta-rule layer (``parallel/kda.py``): heads of ``head_dim``
    keys and values, a causal depthwise convolution of ``conv_width``
    tokens, low-rank decay and output gates of ``gate_rank``, and the
    prefill's chunk."""
    n_heads: int
    head_dim: int
    conv_width: int = 4
    gate_rank: int = 128
    chunk: int = 64

    @property
    def state_shapes(self):
        """What a sequence keeps: the recurrent state (float32) and the
        convolution window (the model's dtype)."""
        width = self.n_heads * self.head_dim
        return ((self.n_heads, self.head_dim, self.head_dim),
                (self.conv_width - 1, 3 * width))


@dataclass(frozen=True)
class MambaConfig:
    """A Mamba-1 selective state-space layer (``parallel/mamba.py``):
    ``d_inner`` channels (``expand`` times the model width), ``d_state``
    states a channel, a causal depthwise convolution of ``conv_width``
    tokens and a step size projected through ``dt_rank``."""
    d_inner: int
    d_state: int = 16
    conv_width: int = 4
    dt_rank: int = 160

    @property
    def state_shapes(self):
        """What a sequence keeps: the state (float32; ``(N, E / L, L)``,
        channels on the lanes: ``ops/ssm_step.py::lanes``) and the
        convolution window (the model's dtype)."""
        from ..ops.ssm_step import lanes
        lane = lanes(self.d_inner)
        return ((self.d_state, self.d_inner // lane, lane),
                (self.conv_width - 1, self.d_inner))


@dataclass(frozen=True)
class Rotary:
    """Rotary positions of one kind of MHA/GQA layer, half-split pairs:
    the first ``fraction`` of each head's columns rotated (the rest carried
    as projected), ``theta``, YaRN ``(factor, original_max_position,
    beta_fast, beta_slow)`` or None for plain, and the factor on cos and
    sin (YaRN's attention factor)."""
    theta: float = 10000.0
    fraction: float = 1.0
    yarn: Optional[Tuple[float, int, float, float]] = None
    attention_factor: float = 1.0


@dataclass(frozen=True)
class LMArch:
    """One LM's block vocabulary.  The defaults ARE the GPT-2-style block
    this package has always run."""
    norm: str = "layernorm"            # | 'rmsnorm'
    norm_eps: float = 1e-5
    mlp: str = "gelu"                  # | 'swiglu'
    attn: str = "mha"          # | 'mla' | 'kda' | 'mamba' ('mha': MHA and GQA)
    layer_kinds: Optional[Tuple[str, ...]] = None   # 'dense' | 'moe' each
    tied_head: bool = True
    embed_scale: bool = True           # embedding times sqrt(d_model)
    mla: Optional[MLAConfig] = None
    moe: Optional[MoEConfig] = None
    attn_kinds: Optional[Tuple[str, ...]] = None    # a kind of ``attn`` each
    kda: Optional[KDAConfig] = None
    # MHA/GQA layers, a value a LAYER (None: no layer has one): the window
    # (None: the whole prefix) and the rotation (None: ``apply_rope`` where
    # the model has no position table, as ever)
    windows: Optional[Tuple[Optional[int], ...]] = None
    rotary: Optional[Tuple[Optional[Rotary], ...]] = None
    attn_gate: bool = False            # per-head sigmoid gate on the context
    attn_bias: bool = True             # False: no b* entries at all
    mamba: Optional[MambaConfig] = None
    # False: no positional signal at all — no table, no rotation
    positions: bool = True

    def kind(self, layer: int) -> str:
        return "dense" if self.layer_kinds is None else \
            self.layer_kinds[layer]

    def attn_kind(self, layer: int) -> str:
        return self.attn if self.attn_kinds is None else \
            self.attn_kinds[layer]

    def window(self, layer: int) -> Optional[int]:
        """Layer ``layer`` sees the last ``window`` tokens (None: all)."""
        if self.windows is None or self.attn_kind(layer) != "mha":
            return None
        return self.windows[layer]

    @property
    def has_ring(self) -> bool:
        """Some layer keeps a ring of its window's rows."""
        return any(self.window(i) for i in range(len(self.windows or ())))


#: the description of every model that passes none
DEFAULT_ARCH = LMArch()


def resolve(arch: Optional[LMArch]) -> LMArch:
    return DEFAULT_ARCH if arch is None else arch


# --------------------------------------------------------------------------
# norms, MLPs, embedding, head
# --------------------------------------------------------------------------

def rms_norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def norm(arch: LMArch, x, p, name: str):
    """``name`` in ``ln1 | ln2 | lnf``: the block's (or model's) norm."""
    if arch.norm == "rmsnorm":
        return rms_norm(x, p[name + "_scale"], arch.norm_eps)
    from .transformer import _layer_norm
    return _layer_norm(x, p[name + "_scale"], p[name + "_bias"])


def swiglu(h, p):
    """``W_down(silu(W_gate h) * W_up h)``, no biases."""
    g = jnp.matmul(h, p["w_gate"], preferred_element_type=jnp.float32)
    u = jnp.matmul(h, p["w_up"], preferred_element_type=jnp.float32)
    a = (jax.nn.silu(g) * u).astype(h.dtype)
    return jnp.matmul(a, p["w_down"],
                      preferred_element_type=jnp.float32).astype(h.dtype)


def ffn(arch: LMArch, layer: int, h, blk, axis_name: str, live=None):
    """The block's second half on normed ``h (..., D)``: ``(y, routing)``;
    ``routing`` is None for a dense layer and, for an expert layer,
    ``(counts, idx)``: its int32 routing-count vector
    (``moe.COUNT_FIELDS`` then one entry per held expert) and the experts
    each token chose, ``(..., top_k)``.  ``live (...) bool`` names the
    rows that carry a token (None: all); the others go to no expert."""
    if arch.kind(layer) == "moe":
        from .moe import moe_dropless
        shape = h.shape
        y, counts, idx = moe_dropless(
            h.reshape(-1, shape[-1]), blk["moe"], arch.moe,
            live=None if live is None else live.reshape(-1))
        return y.reshape(shape), (counts, idx.reshape(shape[:-1] + (-1,)))
    if arch.mlp == "swiglu":
        return swiglu(h, blk["mlp"]), None
    from .tensor_parallel import tp_mlp
    return tp_mlp(h, blk["mlp"], axis_name=axis_name), None


def scale_embedding(arch: LMArch, x, d_model: int):
    return x * (d_model ** 0.5) if arch.embed_scale else x


def head_table(arch: LMArch, params):
    """The ``(V, D)`` table the logits are taken against."""
    return params["embed"] if arch.tied_head else params["head"]


def n_count_entries(arch: LMArch) -> int:
    """Length of the routing-count vector a program of this model returns
    beside its tokens (0: none)."""
    if arch.moe is None:
        return 0
    from .moe import COUNT_FIELDS
    return len(COUNT_FIELDS) + arch.moe.held[1]


def route_shape(arch: LMArch) -> Tuple[int, int]:
    """``(expert layers, top_k)``: the chosen experts a program of this
    model returns for each token it emits; ``(0, 0)`` without experts."""
    if arch.moe is None:
        return 0, 0
    return sum(k == "moe" for k in arch.layer_kinds or ()), arch.moe.top_k


# --------------------------------------------------------------------------
# rotary positions with YaRN
# --------------------------------------------------------------------------

def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_inv_freq(dim: int, theta: float, yarn=None):
    """``(dim/2,)`` float32 inverse frequencies and the cos/sin scale.
    YaRN (Peng et al. 2023, as DeepSeek-V3's modelling code applies it):
    blend ``θ_i`` and ``θ_i / factor`` with a linear ramp between the
    dimensions that turn ``beta_fast`` and ``beta_slow`` times over the
    original context."""
    exponent = jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    extra = 1.0 / (theta ** exponent)
    if yarn is None:
        return extra, 1.0
    factor, orig, beta_fast, beta_slow, mscale, mscale_all_dim = yarn
    inter = extra / factor

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    span = (high - low) if high != low else 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / span,
                    0.0, 1.0)
    keep = 1.0 - ramp                    # 1: keep θ_i, 0: take θ_i / factor
    return (inter * (1.0 - keep) + extra * keep,
            _yarn_mscale(factor, mscale) / _yarn_mscale(factor,
                                                        mscale_all_dim))


def apply_rope_freqs(x, positions, inv_freq, scale: float = 1.0):
    """Rotate ``x (B, S, H, dim)`` at ``positions`` — ``(S,)`` shared or
    ``(B, S)`` per row — with given inverse frequencies; HALF-SPLIT pair
    layout (pair ``i`` is columns ``i`` and ``i + dim/2``), as
    ``transformer.apply_rope``."""
    half = x.shape[-1] // 2
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    if positions.ndim == 2:
        cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    else:
        cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           -1).astype(x.dtype)


def rotate(cfg: Rotary, x, positions):
    """``x (B, S, H, d)`` with the first ``fraction`` of each head's
    columns rotated at ``positions`` and the rest carried as they are."""
    d = x.shape[-1]
    rot = int(d * cfg.fraction)
    inv_freq, _ = rope_inv_freq(
        rot, cfg.theta, None if cfg.yarn is None else cfg.yarn + (0.0, 0.0))
    turned = apply_rope_freqs(x[..., :rot], positions, inv_freq,
                              cfg.attention_factor)
    return turned if rot == d else jnp.concatenate([turned, x[..., rot:]], -1)


def turn_qk(arch: LMArch, layer: int, q, k, positions, rope: bool):
    """A layer's queries and keys ``(B, S, H, d)`` rotated at ``positions``
    as the model says: by the layer's own :class:`Rotary` record (theta,
    the rotated fraction, YaRN) where ``arch.rotary`` names one, else by
    ``transformer.apply_rope`` where ``rope`` (a model without a position
    table), else as they are — as they are, too, for a model that declares
    no positions at all (``arch.positions`` False).  The one rotation of the training loss
    (``transformer.tp_attention``) and of the serving prefill and tick
    (``decode._mha_block``)."""
    if not arch.positions:
        return q, k
    turn = arch.rotary[layer] if arch.rotary is not None else None
    if turn is not None:
        return rotate(turn, q, positions), rotate(turn, k, positions)
    if rope:
        from .transformer import apply_rope
        return apply_rope(q, positions), apply_rope(k, positions)
    return q, k


def window_scope(arch: LMArch, layer: int):
    """``block/attn/window`` around a windowed layer's core stage, in the
    training loss and in the serving prefill and tick alike."""
    return jax.named_scope("block/attn/window") if arch.window(layer) \
        else nullcontext()


def ring_rows(rows, s_real, window: int):
    """The ring a prompt leaves: ``rows (B, S, C)``, position ``p`` at row
    ``p``, of which the first ``s_real (B,)`` are real -> ``(B, window,
    C)`` with ring row ``r`` holding the LAST real position ``p`` with ``p %
    window == r`` (rows ``[max(0, s_real - window), s_real)``; never a
    padded row), zeros where no position has come yet."""
    r = jnp.arange(window)[None, :]
    last = s_real.astype(jnp.int32)[:, None] - 1
    p = r + window * ((last - r) // window)              # (B, window)
    ring = jnp.take_along_axis(
        rows, jnp.clip(p, 0, rows.shape[1] - 1)[..., None], axis=1)
    return jnp.where((r <= last)[..., None], ring, 0)


# --------------------------------------------------------------------------
# multi-head latent attention
# --------------------------------------------------------------------------

def _dense(h, w):
    return jnp.matmul(h, w, preferred_element_type=jnp.float32
                      ).astype(h.dtype)


def mla_project(cfg: MLAConfig, h, a, positions, eps: float):
    """The projections both forms share, from normed ``h (B, S, D)``:
    ``q_nope (B, S, H, nope)``, ``q_rope (B, S, H, rope)`` rotated, and
    the token's cache row parts ``c_kv (B, S, rank)`` normed, ``k_rope
    (B, S, rope)`` rotated (one key for all heads)."""
    b, s, _ = h.shape
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    if cfg.q_lora_rank is None:
        q = _dense(h, a["wq"])
    else:
        q = _dense(rms_norm(_dense(h, a["wdq"]), a["q_norm"], eps), a["wuq"])
    q = q.reshape(b, s, cfg.n_heads, nope + rope)
    ckv = _dense(h, a["wdkv"])
    c_kv = rms_norm(ckv[..., :cfg.kv_lora_rank], a["kv_norm"], eps)
    q_rope, k_rope = q[..., nope:], ckv[..., cfg.kv_lora_rank:]
    if cfg.rope:
        inv_freq, cs = rope_inv_freq(rope, cfg.rope_theta, cfg.yarn)
        q_rope = apply_rope_freqs(q_rope, positions, inv_freq, cs)
        k_rope = apply_rope_freqs(k_rope[:, :, None, :], positions,
                                  inv_freq, cs)[:, :, 0, :]
    return q[..., :nope], q_rope, c_kv, k_rope


def mla_latent_rows(cfg: MLAConfig, c_kv, k_rope):
    """``(B, S, latent_width)`` cache rows ``[c_kv | k_rope | 0 pad]``."""
    pad = cfg.latent_width - cfg.kv_lora_rank - cfg.qk_rope_head_dim
    rows = jnp.concatenate([c_kv, k_rope], -1)
    return jnp.pad(rows, ((0, 0), (0, 0), (0, pad))) if pad else rows


def _wukv(cfg: MLAConfig, a):
    """``W_UKV`` as ``(rank, H, nope + v)``: per head ``[W_UK | W_UV]``."""
    return a["wukv"].reshape(cfg.kv_lora_rank, cfg.n_heads,
                             cfg.qk_nope_head_dim + cfg.v_head_dim)


def mla_attend_prefill(cfg: MLAConfig, q_nope, q_rope, c_kv, k_rope, a,
                       attn_impl: str):
    """PREFILL form: per-head keys and values from the latent, causal
    attention with ``nope + rope``-wide queries/keys and ``v``-wide
    values.  Returns ``ctx (B, S, H·v)``.

    The flash kernel takes one width for q, k and v, so v is zero-padded
    from ``v_head_dim`` to the q/k width inside this call and the pad cut
    off after it: the P·V matmul does ``(nope + rope) / v`` times the
    work it needs (1.5 x at 192 / 128), the Q·K^T matmul none extra.  The
    kernel divides by ``sqrt(width)``; the model's softmax scale is put
    on q."""
    b, s, h, nope = q_nope.shape
    with jax.named_scope("proj"):       # the keys and values of every head
        kv = jnp.einsum("bsr,rhd->bshd", c_kv, _wukv(cfg, a),
                        preferred_element_type=jnp.float32
                        ).astype(c_kv.dtype)
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(k_rope[:, :, None, :],
                              (b, s, h, k_rope.shape[-1]))], -1)
        v = kv[..., nope:]
        q = jnp.concatenate([q_nope, q_rope], -1)
    width = q.shape[-1]
    with jax.named_scope("core"):
        if attn_impl == "flash":
            from ..ops.flash_attention import flash_attention
            qs = (q.astype(jnp.float32)
                  * (cfg.softmax_scale * width ** 0.5)).astype(q.dtype)
            vp = jnp.pad(v, ((0, 0),) * 3 + ((0, width - v.shape[-1]),))
            ctx = flash_attention(qs, k, vp, causal=True)[..., :v.shape[-1]]
        else:
            sc = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                            preferred_element_type=jnp.float32)
            sc = sc * cfg.softmax_scale
            mask = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
            p = jax.nn.softmax(jnp.where(mask[None, None], sc, -1e30),
                               axis=-1)
            ctx = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                             preferred_element_type=jnp.float32
                             ).astype(q.dtype)
        return ctx.reshape(b, s, h * cfg.v_head_dim)


def mla_attend_absorbed(cfg: MLAConfig, q_nope, q_rope, cache, valid, a,
                        use_kernel: bool, busy=None, work=None):
    """DECODE form over the latent ``cache (B, total, latent_width)``:
    ``q_lat = W_UK^T q_nope``, scores against the shared rows, the
    weighted sum of ``c_kv``, then ``W_UV``.  ``valid (B, S_q)`` int32:
    query ``i`` of row ``b`` sees cache rows ``[0, valid[b, i])``.
    Returns ``ctx (B, S_q, H·v)``.  ``use_kernel``: the flash-decode
    kernel (one query per row; ``work``: its work list where the caller
    holds it); else an einsum.  ``busy (B,) bool`` (None: all): the rows
    that attend at all; the others' context is 0 on either path."""
    b, s_q, h, nope = q_nope.shape
    rank = cfg.kv_lora_rank
    with jax.named_scope("proj"):       # W_UK absorbed into the query
        w = _wukv(cfg, a)
        q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, w[..., :nope],
                           preferred_element_type=jnp.float32
                           ).astype(q_nope.dtype)
        pad = cache.shape[-1] - rank - q_rope.shape[-1]
        q_abs = jnp.concatenate([q_lat, q_rope], -1)
        if pad:
            q_abs = jnp.pad(q_abs, ((0, 0),) * 3 + ((0, pad),))
    from ..ops.decode_attention import decode_attend_mla, zero_idle_rows
    with jax.named_scope("core"):
        if use_kernel:
            o_lat = decode_attend_mla(q_abs[:, 0], cache, valid[:, 0] - 1,
                                      busy, rank=rank,
                                      scale=cfg.softmax_scale,
                                      work=work)[:, None]
        else:
            sc = jnp.einsum("bshw,bkw->bhsk", q_abs, cache,
                            preferred_element_type=jnp.float32)
            sc = sc * cfg.softmax_scale
            mask = (jnp.arange(cache.shape[1])[None, None, None, :]
                    < valid[:, None, :, None])
            p = jax.nn.softmax(jnp.where(mask, sc, -1e30), axis=-1)
            # float32 operands: the CPU backend has no bf16 x bf16 -> f32
            # dot of this shape, and this path is the one other backends take
            o_lat = jnp.einsum("bhsk,bkr->bshr", p,
                               cache[..., :rank].astype(jnp.float32)
                               ).astype(q_nope.dtype)
            o_lat = zero_idle_rows(o_lat, busy)
    with jax.named_scope("proj"):       # W_UV on the weighted latent sum
        ctx = jnp.einsum("bshr,rhd->bshd", o_lat, w[..., nope:],
                         preferred_element_type=jnp.float32
                         ).astype(q_nope.dtype)
        return ctx.reshape(b, s_q, h * cfg.v_head_dim)


# --------------------------------------------------------------------------
# what a layer keeps per token, and how a model's parameters are sharded
# --------------------------------------------------------------------------

def _late(module: str, name: str):
    """``parallel/<module>.py::<name>``, looked up where it is first called:
    those modules import this one."""
    def call(*args, **kwargs):
        return getattr(import_module("." + module, __package__), name)(
            *args, **kwargs)
    return call


def _kv_rows(arch: LMArch, layer: int, kv_dim: int, axis_name: str):
    """MHA/GQA: a ``(k, v)`` pair of ROWS, under a window a pair of RINGS."""
    buf = (kv_dim, P(None, None, axis_name))
    if arch.window(layer):
        buf += (arch.window(layer),)
    return (buf, buf)


def _kv_heads(a, head_dim: int) -> int:
    """K/V heads of an MHA/GQA layer, from its weights ``a``."""
    if "wkv" in a:
        return a["wkv"].shape[1] // (2 * head_dim)
    return a["wqkv"].shape[1] // (3 * head_dim)


def _state_and_window(config: str):
    """A state layer: its float32 STATE and its convolution window, by the
    ``state_shapes`` of its numbers, ``LMArch.<config>``."""
    def buffers(arch: LMArch, layer: int, kv_dim: int, axis_name: str):
        state, window = getattr(arch, config).state_shapes
        return ((state, jnp.float32, P()), (window, None, P()))
    return buffers


@dataclass(frozen=True)
class LayerKind:
    """One attention kind, whole: what a layer of it KEEPS and what RUNS it
    on each path.  A new kind is one entry of :data:`LAYER_KINDS`, the module
    that holds its arithmetic, and its numbers' field in :class:`LMArch`."""
    #: ``(arch, layer, kv_dim, axis_name)`` -> the layer's buffer
    #: declarations, in :func:`cache_layout`'s three forms
    buffers: Callable
    #: the serving block, prefill and tick alike: ``(core, x, blk, bufs,
    #: layer, work) -> (x, bufs)`` over the layer's whole buffer tuple
    serve: Callable
    #: the training block's attention half, ``(arch, x, params, layer,
    #: **tp_block's own) -> x``; None: ``tp_block`` refuses the kind by name
    train: Optional[Callable] = None
    #: its prefill leaves a gather nothing wants before the program's end:
    #: ``decode.lm_prefill`` puts a barrier behind such a layer
    deferred_gather: bool = False
    #: ``(attn weights, head_dim)`` -> its K/V heads; None: no per-head K/V
    kv_heads: Optional[Callable] = None


#: every attention kind ``LMArch.attn`` / ``attn_kinds`` may name
LAYER_KINDS = {
    "mha": LayerKind(_kv_rows, _late("decode", "_mha_block"),
                     _late("transformer", "_mha_forward"),
                     kv_heads=_kv_heads),
    # ONE buffer of latent rows, replicated
    "mla": LayerKind(lambda arch, *_: ((arch.mla.latent_width, P()),),
                     _late("decode", "_mla_block"),
                     _late("transformer", "_mla_forward")),
    "kda": LayerKind(_state_and_window("kda"), _late("decode", "_kda_block"),
                     _late("transformer", "_kda_forward")),
    "mamba": LayerKind(_state_and_window("mamba"),
                       _late("decode", "_mamba_block"), deferred_gather=True),
}


def layer_kind(arch: LMArch, layer: int) -> LayerKind:
    """Layer ``layer``'s entry of :data:`LAYER_KINDS` — the one lookup of
    every path; a kind the table lacks is refused by name."""
    kind = arch.attn_kind(layer)
    if kind not in LAYER_KINDS:
        raise NotImplementedError(
            f"layer {layer} is described with attention kind {kind!r}; "
            f"blocks.LAYER_KINDS holds {sorted(LAYER_KINDS)}")
    return LAYER_KINDS[kind]


def cache_layout(arch: LMArch, n_layers: int, kv_dim: int,
                 axis_name: str):
    """Per layer, the buffers its attention keeps (its kind's
    ``LayerKind.buffers``), a tuple of
    declarations of three forms.  ROWS ``(columns, PartitionSpec)``: one row
    a token — the serving pool allocates ``(n_slots, max_total, columns)``
    in its own dtype.  STATE ``(shape, dtype, PartitionSpec)``: one a
    sequence, overwritten in place — the pool allocates ``(n_slots,) +
    shape``; ``dtype`` None is the pool's.  RING ``(columns, PartitionSpec,
    window)``: the last ``window`` rows of a sequence, position ``p`` at
    row ``p % window`` — the pool allocates ``(n_slots, window,
    columns)``."""
    return [layer_kind(arch, i).buffers(arch, i, kv_dim, axis_name)
            for i in range(n_layers)]


def is_state(buf) -> bool:
    """A :func:`cache_layout` declaration of the STATE form."""
    return isinstance(buf[0], tuple)


def is_ring(buf) -> bool:
    """A :func:`cache_layout` declaration of the RING form."""
    return len(buf) == 3 and not is_state(buf)


def buffer_shape(buf, n_slots: int, max_total: int):
    """The pool buffer a declaration asks for (every form)."""
    if is_state(buf):
        return (n_slots,) + tuple(buf[0])
    return (n_slots, buf[2] if is_ring(buf) else max_total, buf[0])


def lm_specs(arch: LMArch, params, axis_name: str):
    """PartitionSpecs of a model's parameters over the model axis.  The
    default description is ``transformer_lm_specs``' Megatron layout; an
    MLA / expert model's attention, router and shared expert are whole on
    every chip (data-parallel, as DeepSeek's own serving runs MLA), so the
    blocks are replicated; the embedding and the head stay vocab-sharded,
    which is what the embedding lookup and the token pick assume."""
    if arch.attn == "mha" and arch.attn_kinds is None and arch.moe is None \
            and arch.mlp == "gelu" and arch.norm == "layernorm" \
            and arch.tied_head and not arch.attn_gate and arch.attn_bias:
        from .transformer import transformer_lm_specs
        return transformer_lm_specs(params, axis_name)
    specs = jax.tree_util.tree_map(lambda _: P(), params)
    for table in ("embed", "head"):
        if table in specs:
            specs[table] = P(axis_name, None)
    return specs
