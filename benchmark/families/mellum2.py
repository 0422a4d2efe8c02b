"""Mellum 2 family (``model_type`` ``mellum``): builds the program's LM train
step through the program's public API (``chainermn_tpu``) from a
configuration file's keys, as ONE CHIP'S SHARE of an expert-parallel
deployment (the configuration's ``deployment``): three sliding-window GQA
layers and one full-attention GQA layer a period, rotary parameters by layer
kind, softmax-routed experts in every layer of which this chip holds the
first ``num_experts_held``, a sliced vocabulary.  The weights come from the
reference's seeded initialiser, so the program and the reference start from
the same numbers and neither takes anything the other made.  Training only.
The trainer is the GPT-2 family's shape (``families/gpt2.py``), copied, not
imported: a family stands alone."""

import os
import sys
from collections import deque
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(_HERE)))

from benchmark.harness import checks as _checks, flops as _flops  # noqa: E402
from benchmark.harness import train_moe_window_costs as _costs  # noqa: E402
from benchmark.harness.loader import module as _module   # noqa: E402
from benchmark.harness.window_kernel_costs import band_pairs  # noqa: E402

from chainermn_tpu.parallel.blocks import MoEConfig       # noqa: E402

if "router" not in getattr(MoEConfig, "__dataclass_fields__", {}):
    # a program before this configuration's PR: fail at once and cleanly,
    # before the reference's minutes
    raise RuntimeError(
        "this program's MoEConfig knows no softmax router: it cannot run "
        "the mellum2 family")

ref = _module("reference", "mellum2")

#: the configuration states bfloat16; the nearest precision below it
CONTROL_PRECISION = "fp8"


def train_flops_per_sample(config, traffic, experts_a_token=None) -> float:
    """Forward + backward FLOPs the algorithm needs for one sequence on
    THIS chip: every weight matmul of attention and router, the HELD
    experts a token a layer (``experts_a_token``; None: even routing,
    ``top_k x held / experts``), the band (sliding layers) and the causal
    triangle (full layers) of the attention, the sliced head.
    Recomputation is not counted."""
    z = ref.sizes(config)
    d, hd, s = z["d"], z["head_dim"], traffic["seq_len"]
    q_cols, kv_cols = z["heads"] * hd, 2 * z["kv_heads"] * hd
    if experts_a_token is None:
        experts_a_token = z["top_k"] * z["held"] / z["experts"]
    per_token_layer = (
        _flops.matmul(1, d, q_cols + kv_cols) + _flops.matmul(1, q_cols, d)
        + _flops.matmul(1, d, z["experts"])
        + experts_a_token * 3 * _flops.matmul(1, d, z["expert_inner"]))
    # QK^T and PV: 4 x head_dim operations a (query, key) pair a head
    pairs = sum(band_pairs(s, z["window"]) if sliding else s * (s + 1) // 2
                for sliding in z["sliding"])
    return _flops.train(
        s * (z["layers"] * per_token_layer + _flops.matmul(1, d, z["vocab"]))
        + 4 * hd * z["heads"] * pairs)


def _key(seed: int):
    return jax.random.PRNGKey(np.uint32(seed % (2 ** 32)))


def _token_pool(ctx, n_batches, batch):
    """``n_batches`` batches of ``(batch, seq_len + 1)`` tokens below the
    sliced vocabulary, made on the device in one call from the seed."""
    seq, vocab = ctx.traffic["seq_len"], ctx.config["vocab_size"]
    make = jax.jit(lambda k: jax.random.randint(
        k, (n_batches, batch, seq + 1), 0, vocab, jnp.int32))
    return make(jax.random.fold_in(_key(ctx.seed), 1))


def arch_of(cfg):
    """The program's description of the model (``parallel/blocks.py``),
    read from the configuration's published keys, a value a LAYER where the
    configuration gives one: the window (``layer_types``) and the rotation
    (``rope_parameters`` by layer kind).  This chip holds the first
    ``num_experts_held`` routed experts (rank 0)."""
    from chainermn_tpu.parallel.blocks import LMArch, Rotary

    def rotary(rp):
        yarn = rp["rope_type"] == "yarn"
        return Rotary(
            theta=float(rp["rope_theta"]),
            yarn=(rp["factor"], rp["original_max_position_embeddings"],
                  rp["beta_fast"], rp["beta_slow"]) if yarn else None,
            attention_factor=rp["attention_factor"] if yarn else 1.0)

    kinds = {kind: rotary(rp) for kind, rp in cfg["rope_parameters"].items()}
    return LMArch(
        norm="rmsnorm", norm_eps=cfg["rms_norm_eps"], mlp="swiglu",
        attn="mha", tied_head=bool(cfg["tie_word_embeddings"]),
        embed_scale=bool(cfg["assumed"]["embed_scale"]),
        layer_kinds=tuple("moe" if t == "sparse" else "dense"
                          for t in cfg["mlp_layer_types"]),
        windows=tuple(cfg["sliding_window"] if t == "sliding_attention"
                      else None for t in cfg["layer_types"]),
        rotary=tuple(kinds[t] for t in cfg["layer_types"]),
        attn_bias=bool(cfg["attention_bias"]),
        moe=MoEConfig(
            n_experts=cfg["num_experts"], top_k=cfg["num_experts_per_tok"],
            n_group=1, topk_group=1, routed_scaling_factor=1.0,
            norm_topk_prob=bool(cfg["norm_topk_prob"]),
            held=(0, cfg.get("num_experts_held", cfg["num_experts"])),
            router="softmax", n_shared=0))


# --------------------------------------------------------------------------
# training
# --------------------------------------------------------------------------

def train_reference(ctx, n_steps: int, precision: str = "float32"):
    """The plain reference's first steps, before the program's state is made."""
    chips = len(ctx.devices)
    pool = _token_pool(ctx, ctx.traffic["pool_batches"],
                       ctx.traffic["batch_per_chip"] * chips)
    out = ref.train_steps(
        _key(ctx.seed), ctx.config, ctx.config["assumed"]["optimizer"],
        [pool[i] for i in range(n_steps)], precision=precision)
    del pool
    return out


def train_compare(want, got):
    """Each number compared, beside its limit: the first gradient's norm
    and the parameters' change by the worst leaf (as every training cell),
    and the share of a batch's (token, layer) pairs whose chosen experts
    differ — of the first step's, at the seeded weights, and of the last
    check step's, at the weights that the steps before it left (their
    backward passes and AdamW moved the router and all below it).  The
    program's routes are the timed step's own output.  The loss is left
    out: ``reference/mellum2.py::LIMITS`` says why."""
    lim = ref.LIMITS
    return [
        _checks.row("grad_norm_gap", ref.worst_leaf_gap(
            got["grad_norms"], want["grad_norms"]), lim["grad_norm_gap"]),
        _checks.row("update_norm_gap", ref.worst_leaf_gap(
            got["update_norms"], want["update_norms"]),
            lim["update_norm_gap"]),
        _checks.row("route_disagreement", ref.route_disagreement(
            got["routes"][0], want["routes"][0]), lim["route_disagreement"]),
        _checks.row("route_disagreement_updated", ref.route_disagreement(
            got["routes"][-1], want["routes"][-1]),
            lim["route_disagreement_updated"]),
    ]


class Trainer:
    """The compiled step with its state: built once, driven through its
    first steps for the check, then handed to the window as it is."""

    def __init__(self, ctx):
        import optax
        from jax.sharding import NamedSharding, PartitionSpec as P

        import chainermn_tpu as mn
        from chainermn_tpu.parallel import (
            make_hybrid_shard_map_step, shard_pytree, state_specs_like,
            tp_transformer_lm_loss)
        from chainermn_tpu.parallel.blocks import lm_specs

        cfg, tr = ctx.config, ctx.traffic
        chips = len(ctx.devices)
        self.ctx = ctx
        self.opt = cfg["assumed"]["optimizer"]
        self.samples_per_step = tr["batch_per_chip"] * chips
        arch = arch_of(cfg)
        mesh = mn.make_nd_mesh(("data", "model"), (chips, 1), ctx.devices)
        self._init = jax.jit(partial(ref.init_params, cfg=cfg))
        params = self._init(_key(ctx.seed))          # float32 masters
        specs = lm_specs(arch, params, "model")
        lm_loss = partial(tp_transformer_lm_loss, head_dim=cfg["head_dim"],
                          axis_name="model", attn_impl=tr["attn_impl"],
                          ce_impl=tr["ce_impl"], arch=arch,
                          remat=tr["remat"] == "layer")
        bf16 = lambda p: jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16), p)

        def loss_fn(p, batch):      # bfloat16 compute on float32 masters
            return lm_loss(bf16(p), batch, aux=True)

        optimizer = optax.adamw(self.opt["lr"], b1=self.opt["b1"],
                                b2=self.opt["b2"], eps=self.opt["eps"],
                                weight_decay=self.opt["weight_decay"])
        step = make_hybrid_shard_map_step(
            loss_fn, optimizer, mesh, params, specs, data_axis="data",
            batch_spec=P("data"), has_aux=True,
            aux_specs={"counts": P(), "routes": P("data")})
        self.p = shard_pytree(params, mesh, specs)
        self.st = shard_pytree(jax.jit(optimizer.init)(params), mesh,
                               state_specs_like(optimizer, params, specs))
        pool = _token_pool(ctx, tr["pool_batches"], self.samples_per_step)
        sharding = NamedSharding(mesh, P("data"))
        self.pool = [(jax.device_put(pool[i], sharding),)
                     for i in range(tr["pool_batches"])]
        del pool, params
        self.compiled = step.lower(self.p, self.st, self.pool[0]).compile()
        text = self.compiled.as_text()
        mem = self.compiled.memory_analysis()
        self.info = {
            "tpu_custom_calls": text.count("tpu_custom_call"),
            "kernels": {name: text.count(name) for name in (
                "window_flash_fwd", "window_flash_bwd", "flash_fwd",
                "flash_bwd", "moe_gmm", "moe_gmm_dw", "fused_ce")},
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
        }
        if ctx.on_tpu and not all(self.info["kernels"].values()):
            raise RuntimeError("the step lost its Pallas kernels: "
                               f"{self.info}")
        # the step hands out its routing (the aux: counts summed over the
        # layers, the chosen experts a token a layer).  The CHECK's steps'
        # counts go to the program's counters (``train/moe_*``: the routing
        # at and just after the seeded weights) and their routes to the
        # comparison; the counts of the steps dispatched inside the traced
        # slice go to the harness (``book_slice``: the steps whose kernels
        # the trace times — the router LEARNS in between, PERF.md, Findings
        # PR 38); no other step's aux is read back
        mn.observability.enable()
        _costs.reset_slice()
        self._slice = deque()
        self._checked = None          # a list while the check's steps run
        self.n = 0

    @property
    def flops_per_sample(self) -> float:
        """Needed FLOPs a sequence, the held experts' part at the routing
        of the traced slice's steps where there was one (a run without a
        slice: even routing)."""
        self._book_ready_slice(wait=True)
        cfg, tr = self.ctx.config, self.ctx.traffic
        held = _costs.held_assignments_per_step()
        tokens = self.samples_per_step * tr["seq_len"]
        return train_flops_per_sample(
            cfg, tr, held and held / (tokens * cfg["num_hidden_layers"]))

    def _book_ready_slice(self, wait: bool = False):
        """Book the slice's kept routing counts whose step's result is
        ALREADY on the host (``wait``: of every kept step): the timed loop
        never waits for the device here."""
        while self._slice and (wait or self._slice[0].is_ready()):
            _costs.book_slice(np.asarray(self._slice.popleft()))

    def step(self):
        """One train step on the pool's next batch; the loss stays on the
        device."""
        batch = self.pool[self.n % len(self.pool)]
        self.n += 1
        with self.ctx.spans.span("dispatch"):
            self.p, self.st, loss, aux = self.compiled(
                self.p, self.st, batch)
        if self._checked is not None:
            self._checked.append(aux)
        elif self.ctx.tracer.active:
            aux["counts"].copy_to_host_async()
            self._slice.append(aux["counts"])
        self._book_ready_slice()
        return loss

    def first_steps(self, n_steps: int):
        import optax

        from chainermn_tpu.parallel.moe import book_routing_counts

        losses, grad_norms = [], None
        norms = jax.jit(ref.leaf_norms)
        self._checked = []
        for _ in range(n_steps):
            losses.append(float(self.step()))
            if grad_norms is None:    # AdamW's first moment is (1 - b1) g
                mu = optax.tree_utils.tree_get(self.st, "mu")
                grad_norms = jax.device_get(norms(mu)) / (1 - self.opt["b1"])
        checked, self._checked = self._checked, None
        for aux in checked:
            book_routing_counts(np.asarray(aux["counts"]))
        change = jax.jit(lambda p, k: ref.leaf_norms(jax.tree_util.tree_map(
            jnp.subtract, p, self._init(k))))
        update_norms = jax.device_get(change(self.p, _key(self.ctx.seed)))
        return {"losses": losses, "grad_norms": grad_norms,
                "update_norms": update_norms,
                "routes": [np.asarray(aux["routes"]) for aux in checked]}


def build_trainer(ctx):
    return Trainer(ctx)
