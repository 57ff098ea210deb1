"""Runner of language-model training cells.

The program under test is the LM training path as users run it: the state
from ``repro.train.state.init_state``, the step from
``repro.train.step.make_train_step`` (AdamW, global-norm clip) through
``repro.models.transformer.lm_apply``, jitted with the state donated, one
step a dispatch and its loss fetched. Set-up makes the weights from the
seed on the device with the reference's ``init_params``, rounds them to
the program's dtypes and puts them in ``init_state``'s tree, makes the
traffic's batches (token ids drawn from a Zipf law over the vocabulary,
targets the next id), compiles the step and drives the compared first
steps: they are what ``correct`` compares with the plain reference, which
starts from the same rounded weights in float32 and takes the same
batches.

The reference lays its weights out as the program's parameter tree:
``embed``, ``head``, ``final_norm``, ``lead/slot0`` (the dense layer) and
``blocks/slot0`` (the MoE layers, stacked on a leading layer axis).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness, lmwork
from bench.reference import deepseek_v2 as ref
from repro.configs import registry
from repro.models import mla
from repro.optim.optimizers import adamw
from repro.train import state as state_lib
from repro.train import step as step_lib

# The control: the reference in the program's place with every product's
# inputs rounded to float8 e4m3, the precision below the configuration's
# bfloat16.
CONTROL = {"precision": "float8"}


def faults(t: dict) -> dict:
    """Faults planted in the reference put in the program's place: half of
    each batch's sequences, and the state left unchanged (no step taken)."""
    return {"half_batch": {"keep": 0.5}, "unchanged": {"lr": 0.0}}


def program_config(c: dict):
    """The program's ``ModelConfig`` for the configuration file: the
    registered architecture (its layer kinds and choices) with every size
    the file states, the cuts (depth, experts held, vocabulary) among them.
    A setting the program holds fixed must read as the file states it.
    """
    y = c["rope_scaling"]
    fixed = {"routed_scaling_factor": (c["routed_scaling_factor"], 1),
             "rope_scaling.mscale": (y["mscale"], y["mscale_all_dim"]),
             "rope_scaling.beta_fast": (y["beta_fast"], mla.YARN_BETA_FAST),
             "rope_scaling.beta_slow": (y["beta_slow"], mla.YARN_BETA_SLOW)}
    adam = adamw.__kwdefaults__
    fixed["adamw"] = ((c["adam_b1"], c["adam_b2"], c["weight_decay"]),
                      (adam["b1"], adam["b2"], adam["weight_decay"]))
    for name, (got, held) in fixed.items():
        if got != held:
            raise ValueError(f"the file's {name} is {got}; the program "
                             f"holds {held}")
    cfg = registry.get_config(c["arch"])
    return cfg.with_updates(
        n_layers=c["num_hidden_layers"], lead=cfg.lead[:1] *
        c["first_k_dense_replace"], vocab=c["vocab_size"],
        experts_held=c["n_routed_experts"], expert_first=c["expert_first"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        moe_d_ff=c["moe_intermediate_size"],
        shared_d_ff=c["moe_intermediate_size"] * c["n_shared_experts"],
        n_experts=c["router_experts"], top_k=c["num_experts_per_tok"],
        kv_lora_rank=c["kv_lora_rank"], qk_nope_dim=c["qk_nope_head_dim"],
        qk_rope_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        norm_eps=c["rms_norm_eps"], rope_theta=float(c["rope_theta"]),
        moe_renorm=c["norm_topk_prob"],
        tie_embeddings=c["tie_word_embeddings"], mlp_act=c["hidden_act"],
        aux_weight=c["aux_loss_alpha"], yarn_factor=float(y["factor"]),
        yarn_original_max=y["original_max_position_embeddings"],
        yarn_mscale_all_dim=y["mscale_all_dim"])


def initial_weights(c: dict, key):
    """The reference's initial weights (``ref.init_params``, the file's
    ``assumed`` init) rounded to the program's dtype of each leaf: what the
    program starts from, and, cast back to float32, the reference."""
    abstract = state_lib.abstract_state(program_config(c)).params
    return jax.jit(lambda k: jax.tree.map(
        lambda a, t: a.astype(t.dtype), ref.init_params(k, c), abstract))(key)


def make_batches(c: dict, t: dict, key) -> list:
    """``t['batches']`` batches of (tokens, targets), each (B, S) int32 on
    the device: ids drawn i.i.d. from Zipf(``zipf_s``) over the vocabulary
    (id r - 1 with probability proportional to r^-s), targets the next id
    of the same draw."""
    v, b, s = c["vocab_size"], t["batch"], t["seq_len"]
    cdf = jnp.cumsum(jnp.arange(1, v + 1, dtype=jnp.float32) ** -t["zipf_s"])
    cdf = cdf / cdf[-1]

    @jax.jit
    def one(k):
        ids = jnp.searchsorted(cdf, jax.random.uniform(k, (b, s + 1)))
        ids = jnp.minimum(ids, v - 1).astype(jnp.int32)
        return ids[:, :-1], ids[:, 1:]
    return [one(k) for k in jax.random.split(key, t["batches"])]


def _feed(batch) -> dict:
    tokens, targets = batch
    b, s = tokens.shape
    return {"tokens": tokens, "targets": targets,
            "positions": jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32),
                                          (b, s))}


def _keys(seed: int):
    return jax.random.split(harness.seed_key(seed))


def _leaf_norms_np(tree) -> dict:
    return {jax.tree_util.keystr(k): float(np.linalg.norm(
        np.asarray(v, np.float32))) for k, v in
        jax.tree_util.tree_flatten_with_path(tree)[0]}


class Program:
    """The compiled step and its state, brought up from a seed. ``work``
    collects what the readers need: the step's compiled text (traced runs)
    and each dispatch's counters."""

    def __init__(self, c: dict, t: dict, seed: int, work: dict | None = None,
                 hlo_text: bool = False):
        self.c, self.t = c, t
        cfg = program_config(c)
        key_w, key_d = _keys(seed)
        state = jax.jit(lambda k, p: state_lib.init_state(k, cfg)._replace(
            params=p), donate_argnums=1)(key_w, initial_weights(c, key_w))
        self.params0 = jax.device_get(state.params)
        self.batches = [_feed(b) for b in make_batches(c, t, key_d)]
        step = step_lib.make_train_step(cfg, lr=c["lr"], clip=c["clip"])
        self.work = {} if work is None else work
        self.work.update(routed=[], peak=[], call_s=[])
        keyed = jax.config.jax_compilation_cache_include_metadata_in_key
        # a traced run maps the trace's operations through this program's
        # own op_name metadata, which the cache key leaves out by default
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          hlo_text)
        try:
            self.step = jax.jit(step, donate_argnums=0).lower(
                state, self.batches[0]).compile()
        finally:
            jax.config.update(
                "jax_compilation_cache_include_metadata_in_key", keyed)
        if hlo_text:
            self.work["hlo"] = self.step.as_text()
        self.state, self.calls = state, 0

    def window_call(self) -> list:
        batch = self.batches[self.calls % len(self.batches)]
        t0 = time.perf_counter()
        self.state, m = self.step(self.state, batch)
        self.calls += 1
        got = jax.device_get({k: m[k] for k in ("loss", "moe_routed",
                                                "moe_peak")})
        self.work["call_s"].append(time.perf_counter() - t0)
        self.work["routed"].append(got["moe_routed"].tolist())
        self.work["peak"].append(got["moe_peak"].tolist())
        return [float(got["loss"])]

    def first_steps(self) -> dict:
        """Drive the compared steps and read each step's loss, the first
        step's clipped gradient per leaf (from AdamW's second moment) and
        each leaf's change."""
        b2 = adamw.__kwdefaults__["b2"]
        losses, grad = [], None
        for _ in range(self.t["compare_steps"]):
            losses.extend(self.window_call())
            if grad is None:     # |g| per element is sqrt(nu / (1 - b2))
                grad = _flat(jax.jit(lambda nu: jax.tree.map(
                    lambda v: jnp.sqrt(jnp.sum(v) / (1 - b2)), nu))(
                        self.state.opt.nu))
        delta = _leaf_norms_np(jax.tree.map(
            lambda a, b: np.asarray(a, np.float32) - np.asarray(b, np.float32),
            jax.device_get(self.state.params), self.params0))
        harness.log("held-expert assignments a layer, steps 1-%d:"
                    % len(losses), self.work["routed"],
                    "largest load over mean:", self.work["peak"])
        return {"loss": losses, "grad": grad, "delta": delta}


def program_observables(c: dict, t: dict, seed: int) -> dict:
    return Program(c, t, seed).first_steps()


def reference_observables(c: dict, t: dict, seed: int, *,
                          precision: str = "highest", keep: float = 1.0,
                          lr: float | None = None) -> dict:
    """The same readings from the plain reference (or, with another
    ``precision``, ``keep`` or ``lr``, from the control or a planted
    fault), from the program's initial weights in float32 and the same
    batches."""
    key_w, key_d = _keys(seed)
    p0 = jax.device_get(jax.jit(lambda p: jax.tree.map(
        lambda a: a.astype(jnp.float32), p), donate_argnums=0)(
            initial_weights(c, key_w)))
    batches = make_batches(c, t, key_d)[:t["compare_steps"]]
    losses, grad, params = ref.train(
        jax.device_put(p0), batches, c if lr is None else dict(c, lr=lr),
        product_kind=precision, keep=keep)
    delta = jax.jit(lambda a, b: jax.tree.map(
        lambda x, y: jnp.linalg.norm(x - y), a, b))(params, p0)
    return {"loss": losses, "grad": _flat(grad), "delta": _flat(delta),
            "taken": None}


def _flat(tree) -> dict:
    return {jax.tree_util.keystr(k): float(v) for k, v in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


def rehearse(c: dict, t: dict, devices) -> dict:
    """Compile, for the described ``devices``, the program's step and the
    reference's gradient of one sequence at the cell's shapes."""
    from jax.sharding import SingleDeviceSharding
    from repro.kernels.flash_attention import ops as flash_ops
    sharding = SingleDeviceSharding(devices[0])

    def spec(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=sharding), tree)
    cfg = program_config(c)
    state = spec(state_lib.abstract_state(cfg))
    batch = spec(jax.eval_shape(lambda: _feed((
        jnp.zeros((t["batch"], t["seq_len"]), jnp.int32),) * 2)))
    interpret = flash_ops.default_interpret
    flash_ops.default_interpret = lambda: False
    try:
        step = jax.jit(step_lib.make_train_step(cfg), donate_argnums=0)
        out = {"program step": step.lower(state, batch).compile()}
    finally:
        flash_ops.default_interpret = interpret
    f32 = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, jnp.float32, sharding=sharding), state.params)
    seq = jax.ShapeDtypeStruct((t["seq_len"],), jnp.int32, sharding=sharding)
    grad = ref._grad_fn(c, "highest", t["batch"], t["batch"] * t["seq_len"])
    out["reference sequence gradient"] = grad.lower(
        (f32, jax.ShapeDtypeStruct((), jnp.float32, sharding=sharding)),
        f32, seq, seq).compile()
    return out


def run(run: harness.Run) -> dict:
    c, t = run.config, run.traffic
    devs = harness.devices(run)
    work = lmwork.step_work(c, t)
    tokens = t["batch"] * t["seq_len"]      # a step, one loss each

    def rates(updates, elapsed):
        # an LM trains one step of its token-level MDP a token: the
        # benchmark's env_steps_per_s is tokens trained a second here
        return {"env_steps_per_s": updates * tokens / elapsed}
    # execute holds the only reference to the program, and frees it before
    # the reference runs
    line = harness.execute(
        run, devs, Program(c, t, run.seed, work, hlo_text=run.trace),
        lambda: reference_observables(c, t, run.seed), work, rates)
    calls = work["routed"][t["compare_steps"]:]
    if calls:
        harness.log("window: held-expert assignments a layer, mean",
                    np.mean(calls, axis=0).round(1).tolist(),
                    "largest load over mean, max",
                    np.max(work["peak"][t["compare_steps"]:], axis=0).tolist(),
                    "seconds a call", np.round(work["call_s"][
                        t["compare_steps"]:], 4).tolist())
    return line
