"""GPT-2 family: builds the program's LM train step and serving engine
through the program's public API (``chainermn_tpu``), from a configuration
file's sizes.  Set-up code copied from ``chip_smoke.py`` (``_lm_setup``,
``phase_serve``), not imported.  The weights come from the reference's
seeded initialiser, so the program and the reference start from the same
numbers and neither takes anything the other made."""

import os
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(_HERE)))

from benchmark.harness import checks as _checks, flops as _flops  # noqa: E402
from benchmark.harness.loader import module as _module   # noqa: E402

ref = _module("reference", "gpt2")

#: the configuration states bfloat16; the nearest precision below it
CONTROL_PRECISION = "fp8"


def train_flops_per_sample(config, traffic) -> float:
    """Forward + backward FLOPs the algorithm needs for one sequence: every
    weight matmul, the tied head at the published vocabulary, and causal
    attention (half of the square).  Recomputation is not counted."""
    d, L, inner = config["n_embd"], config["n_layer"], config["n_inner"]
    s, v = traffic["seq_len"], config["vocab_size"]
    per_token = L * (_flops.matmul(1, d, 3 * d) + _flops.matmul(1, d, d)
                     + 2 * _flops.matmul(1, d, inner)) + _flops.matmul(1, d, v)
    attention = L * 2 * _flops.matmul(1, d, s) / 2     # QK^T and PV, causal
    return _flops.train(s * (per_token + attention))


def _key(seed: int):
    return jax.random.PRNGKey(np.uint32(seed % (2 ** 32)))


def _token_pool(ctx, n_batches, batch):
    """``n_batches`` batches of ``(batch, seq_len + 1)`` tokens, made on the
    device in one call from the seed, rows all different."""
    seq, vocab = ctx.traffic["seq_len"], ctx.config["vocab_size"]
    make = jax.jit(lambda k: jax.random.randint(
        k, (n_batches, batch, seq + 1), 0, vocab, jnp.int32))
    return make(jax.random.fold_in(_key(ctx.seed), 1))


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
        [pool[i] for i in range(n_steps)],
        rows_per_block=ctx.traffic.get("reference_rows_per_block", 2),
        precision=precision)
    del pool
    return out


def train_compare(want, got):
    """Each number compared, beside its limit."""
    return _checks.training(ref, want, got)


class Trainer:
    """The compiled step with its state: built once, driven through its
    first steps for the check, then handed to the window as it is."""

    def __init__(self, ctx):
        import optax
        from jax.sharding import PartitionSpec as P

        import chainermn_tpu as mn
        from chainermn_tpu.parallel import (
            make_hybrid_shard_map_step, shard_pytree, state_specs_like,
            tp_transformer_lm_loss, transformer_lm_specs)

        cfg, tr = ctx.config, ctx.traffic
        chips = len(ctx.devices)
        self.ctx = ctx
        self.opt = cfg["assumed"]["optimizer"]
        self.samples_per_step = tr["batch_per_chip"] * chips
        self.flops_per_sample = train_flops_per_sample(cfg, tr)
        mesh = mn.make_nd_mesh(("data", "model"), (chips, 1), ctx.devices)
        self._init = jax.jit(partial(ref.init_params, cfg=cfg))
        params = self._init(_key(ctx.seed))          # float32 masters
        specs = transformer_lm_specs(params, "model")
        lm_loss = partial(tp_transformer_lm_loss,
                          head_dim=cfg["n_embd"] // cfg["n_head"],
                          axis_name="model", attn_impl=tr["attn_impl"],
                          ce_impl=tr["ce_impl"])

        def loss_fn(p, batch):      # bfloat16 compute on float32 masters
            return lm_loss(jax.tree_util.tree_map(
                lambda a: a.astype(jnp.bfloat16), p), batch)

        optimizer = optax.adamw(self.opt["lr"], b1=self.opt["b1"],
                                b2=self.opt["b2"], eps=self.opt["eps"],
                                weight_decay=self.opt["weight_decay"])
        step = make_hybrid_shard_map_step(
            loss_fn, optimizer, mesh, params, specs, data_axis="data",
            batch_spec=P("data"))
        self.p = shard_pytree(params, mesh, specs)
        self.st = shard_pytree(jax.jit(optimizer.init)(params), mesh,
                               state_specs_like(optimizer, params, specs))
        pool = _token_pool(ctx, tr["pool_batches"], self.samples_per_step)
        from jax.sharding import NamedSharding
        sharding = NamedSharding(mesh, P("data"))
        self.pool = [(jax.device_put(pool[i], sharding),)
                     for i in range(tr["pool_batches"])]
        del pool, params
        self.compiled = step.lower(self.p, self.st, self.pool[0]).compile()
        text = self.compiled.as_text()
        mem = self.compiled.memory_analysis()
        self.info = {
            "tpu_custom_calls": text.count("tpu_custom_call"),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
        }
        if ctx.on_tpu and self.info["tpu_custom_calls"] < 2 * cfg["n_layer"] + 2:
            raise RuntimeError("the step lost its Pallas kernels: "
                               f"{self.info}")
        self.n = 0

    def step(self):
        """One train step on the pool's next batch; the loss stays on the
        device."""
        batch = self.pool[self.n % len(self.pool)]
        self.n += 1
        with self.ctx.spans.span("dispatch"):
            self.p, self.st, loss = self.compiled(self.p, self.st, batch)
        return loss

    def first_steps(self, n_steps: int):
        import optax

        losses, grad_norms = [], None
        norms = jax.jit(ref.leaf_norms)
        for _ in range(n_steps):
            losses.append(float(self.step()))
            if grad_norms is None:    # AdamW's first moment is (1 - b1) g
                mu = optax.tree_utils.tree_get(self.st, "mu")
                grad_norms = jax.device_get(norms(mu)) / (1 - self.opt["b1"])
        change = jax.jit(lambda p, k: ref.leaf_norms(jax.tree_util.tree_map(
            jnp.subtract, p, self._init(k))))
        update_norms = jax.device_get(change(self.p, _key(self.ctx.seed)))
        return {"losses": losses, "grad_norms": grad_norms,
                "update_norms": update_norms}


def build_trainer(ctx):
    return Trainer(ctx)


# --------------------------------------------------------------------------
# serving
# --------------------------------------------------------------------------

class Server:
    """The program's ``ServingEngine`` at the configuration's sizes, with
    bfloat16 weights made on the device from the seed."""

    #: this family's own servers alone warm a prefix hit: the families that
    #: build on this class do not run this ``__init__`` and keep their
    #: set-up as it was measured
    warm_prefix_hit = False

    def __init__(self, ctx):
        import chainermn_tpu as mn
        from chainermn_tpu.serving import ServingEngine

        cfg, eng = ctx.config, dict(ctx.traffic["engine"])
        self.vocab = cfg["vocab_size"]
        params = jax.jit(partial(ref.init_params, cfg=cfg,
                                 dtype=jnp.bfloat16))(_key(ctx.seed))
        mesh = mn.make_nd_mesh(("model",), (1,), ctx.devices[:1])
        self.eng = ServingEngine(
            params, head_dim=cfg["n_embd"] // cfg["n_head"], mesh=mesh, **eng)
        del params
        self.warm_prefix_hit = True
        self.info = {"engine": eng, "prefix_cache": True}

    def warm(self, prompt_lens):
        """One request per prefill program the traffic uses, a few ticks
        each: exactly the cell's shapes, no others.  Where
        ``warm_prefix_hit``, the first prompt once more: it finds its own
        rows in the prefix cache, so the engine builds
        ``serving_prefix_copy`` here and not at the window's first hit
        (``--seed 0`` draws its first prompt from this warm-up's own stream,
        hit the cache and compiled inside the window: PERF.md, Findings
        PR 46)."""
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, self.vocab, n, dtype=np.int32)
                   for n in prompt_lens]

        def serve(batch):
            handles = [self.eng.submit(p, 4) for p in batch]
            while not self.idle():
                self.eng.step()
            return handles

        handles = serve(prompts)
        if self.warm_prefix_hit:
            handles += serve(prompts[:1])
        bad = [h.status for h in handles if h.status != "done"]
        if bad:
            raise RuntimeError(f"warm-up requests did not finish: {bad}")
        self.eng.reset_stats()

    def submit(self, prompt, max_new, on_token):
        """The request's handle, or None where the engine refused it."""
        from chainermn_tpu.serving import AdmissionError

        try:
            return self.eng.submit(prompt, max_new, on_token=on_token)
        except AdmissionError:
            return None

    def step(self):
        self.eng.step()
        # WORKAROUND for a fault of the program, found by this benchmark's
        # check (PERF.md, Findings PR 24): the tick advances the position of
        # EVERY slot, free ones too, without bound.  Past n_positions the
        # learned position lookup reads out of range (NaN), the NaN K/V is
        # written to the slot's last row, and every later request in that
        # slot emits the no-winner sentinel 2**30.  Holding idle slots at
        # the last row keeps their garbage finite; a busy slot never
        # reaches it (prompt + answer <= max_total).  No-op once the
        # program bounds the position itself.
        pool = self.eng.pool
        if pool.pos.max() >= pool.max_total:
            pool.pos = np.minimum(pool.pos, pool.max_total - 1)

    def idle(self) -> bool:
        return (self.eng.scheduler.queue_depth == 0
                and self.eng.pool.busy_count == 0)

    def busy_slots(self) -> int:
        return self.eng.pool.busy_count

    def backlog(self) -> int:
        return self.eng.scheduler.queue_depth + self.eng.pool.busy_count

    def metrics(self):
        return self.eng.metrics()

    def close(self):
        self.eng.close()
        self.eng = None


def build_server(ctx):
    return Server(ctx)


def served_sample(ctx, recs, reqs, k: int):
    """``k`` finished requests drawn from the seed, the longest among them:
    each as (prompt + emitted tokens, prompt length)."""
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
        recs[i]["handle"].tokens, np.int32)]), len(reqs[i]["prompt"]))
        for i in picked]


def serve_compare(ctx, sample, precision=None):
    """The reference's one forward over each sampled prompt with its served
    tokens (after the engine is freed): the widest gap by which a served
    token's logit lies below the reference's best."""
    cfg = ctx.config
    lim = ref.LIMITS["served_logit_gap"]
    if not sample:
        return [_checks.row("served_logit_gap", float("nan"), lim)]
    width = cfg["n_positions"] + 1
    tokens = np.zeros((len(sample), width), np.int32)
    for i, (seq, _) in enumerate(sample):
        tokens[i, : len(seq)] = seq
    params = jax.jit(partial(ref.init_params, cfg=cfg,
                             dtype=jnp.bfloat16))(_key(ctx.seed))
    gap, agree = ref.served_gaps(
        params, cfg, tokens, [p for _, p in sample],
        [len(s) for s, _ in sample], precision=precision)
    n_tok = sum(len(s) - p for s, p in sample)
    ctx.say(f"reference: {len(sample)} served requests, {n_tok} served "
            f"tokens, exact argmax agreement {agree:.4f}")
    return [_checks.row("served_logit_gap", gap, lim)]
