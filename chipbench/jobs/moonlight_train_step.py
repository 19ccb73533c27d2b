"""Job ``moonlight_train_step``: one training step of a Moonlight
(``deepseek_v3``) causal language model (latent attention in every layer, its
shared key part and the matching part of every query head rotated, one dense
layer, then sigmoid-routed experts beside shared ones) through
``ht.nn.DataParallel.make_train_step``: forward, next-token loss, backward and
the AdamW update in one jitted program, parameters and optimizer state
donated.  The loss is ``PatternLM.next_token_loss``'s (the final norm, the
head's product and the log-sum-exp ``loss_block_rows`` rows at a time),
handed to the step as ``forward=``.  The step's batches, tallies and counters
are ``lm_train_step``'s and the sums a step reports by parameter group
``kimi_linear_train_step``'s; the model, the reference
(``references/moonlight.py``), the limits and the count of work are this
file's.

Configuration keys: the public ``config.json``'s own (``hidden_size``,
``kv_lora_rank``, ``qk_rope_head_dim``, ``first_k_dense_replace``,
``n_routed_experts``, ``n_shared_experts``, ``routed_scaling_factor`` ...,
read by ``model()`` and by the reference), ``n_routed_experts`` being the
experts held here; the names under ``aliases`` (``num_experts``,
``num_experts_routed``: the router's width, ``experts_held``,
``layer_types``); ``kv_a_layernorm_eps`` (the latent norm's), ``expert_rows_bound``
(the hard size of an expert layer's buffers), ``loss_block_rows``,
``activation_dtype``, ``init_std`` and ``optimizer``
(AdamW's ``lr``, ``b1``, ``b2``, ``eps``, ``weight_decay`` and
``warmup_steps``: step ``t`` from 1 uses ``lr * min(1, t / warmup_steps)``).
Traffic keys: ``sequences``, ``sequence_length``, ``zipf_exponent``,
``check_steps``.  The batch of step ``i`` is drawn on the device from ``(seed,
i)`` inside the job: token ids Zipf over the vocabulary, id 0 the most
frequent, no padding.

The initial parameters are the reference's draw from ``(seed, configuration)``
(``reference.init_params``) with each expert layer's selection bias set to even
out the experts' loads on a batch of the seed's that no step trains on
(``reference.even_out_bias``), handed to the trainer as a checkpoint would be.
``check`` replays the first ``check_steps`` steps from the same seeded
parameters and batches with the plain float32 reference (dense masked
attention, the published rotation, a loop over the experts held) and its
plain AdamW, a sequence at a time, and compares loss, routed rows and, by
parameter group, gradient norms, the parameters' steps, both moments and the
decay with what the timed path returned; a run that dropped one row of a held
expert is not correct.
"""

from __future__ import annotations

import functools
import types

import jax
import jax.numpy as jnp
import numpy as np

import heat_tpu as ht
from chipbench.jobs.kimi_linear_train_step import _stats
from chipbench.jobs.lm_train_step import (  # noqa: F401  (job and counters are this job kind's too)
    _batches, _rate, _router_with_experts, _worst, counters, job)
from chipbench.jobs.smallthinker_train_step import attended_pairs
from chipbench.jobs.trinity_train_step import _loss
from chipbench.references import moonlight as reference
from heat_tpu.nn.models import PatternLM

# The timed path keeps float32 parameters and multiplies bfloat16 operands into
# float32 sums; the reference is float32 throughout.  Each limit lies between the
# largest reading of twelve sound runs on a TPU v5e (seven with the selection
# biases evened out, five with them drawn) and what the reference with float8
# operands reads, which fails all six; the program built without the rotation
# fails all six and a program whose loss covers two of the four sequences fails
# five (PERF.md has the readings).  The reference with bfloat16 operands is the
# timed path's own rounding and passes.
LIMITS = {
    # |loss - reference| / reference, worst of the replayed steps
    "loss_err": 4e-4,
    # |norm - reference| / reference, worst parameter group and step
    "grad_norm_err": 0.1,
    # |rows - reference| summed over the experts held / rows routed, worst layer and
    # step (a selection made from bfloat16 operands differs where two scores nearly tie)
    "routed_rows_err": 0.1,
    # | |p' - p| - reference's | / reference's, worst parameter group and step; a
    # state left unchanged reads 1
    "update_err": 2.5e-2,
    # the same of AdamW's new moments m and v
    "moment_err": 0.25,
    # (p' - p) . p against the reference's in units of lr * weight_decay * |p|^2,
    # worst group (the router with its experts) and step
    "decay_err": 0.2,
    # rows of held experts that no expert computed, all steps of the run: the
    # buffers' hard size (``expert_rows_bound``) holds a row for every token-slot
    "dropped_rows": 0,
    # 1 where the last timed step's loss is not finite
    "loss_not_finite": 0,
}


def model(config: dict) -> PatternLM:
    dtype = config.get("activation_dtype")
    lo, hi = config["experts_held"]
    if (hi - lo != config["n_routed_experts"] or config["num_experts"] != config["n_routed_experts"]
            or config["layer_types"] != ["mla"] * config["num_hidden_layers"]):
        raise ValueError("an alias in the configuration differs from the published key it stands for")
    return PatternLM(
        config["vocab_size"], config["hidden_size"], config["layer_types"],
        num_heads=config["num_attention_heads"], kv_rank=config["kv_lora_rank"],
        qk_nope_dim=config["qk_nope_head_dim"], qk_shared_dim=config["qk_rope_head_dim"],
        v_dim=config["v_head_dim"], rope_kinds=("mla",), rope_base=config["rope_theta"],
        kv_norm_eps=config["kv_a_layernorm_eps"],
        ffn_dim=config["intermediate_size"], num_dense_layers=config["first_k_dense_replace"],
        num_experts=config["num_experts_routed"], experts_per_token=config["num_experts_per_tok"],
        expert_dim=config["moe_intermediate_size"], experts_held=range(lo, hi),
        routed_scaling=config["routed_scaling_factor"], norm_topk=config["norm_topk_prob"],
        shared_expert_dim=config["n_shared_experts"] * config["moe_intermediate_size"],
        expert_rows_bound=config.get("expert_rows_bound"), norm_eps=config["rms_norm_eps"],
        init_std=config["init_std"],
        dtype=None if dtype is None else jnp.dtype(dtype), tie_embedding=config["tie_word_embeddings"])


# the index of the batch that evens out the selection biases: a step no run reaches
EVEN_OUT_BATCH = 1 << 30


def _draw(config: dict, batch):
    """``key -> parameters``: the reference's draw with the selection biases
    evened out on ``batch(EVEN_OUT_BATCH)``, on the device in one program (the
    batch an argument, so that one compiled program serves every seed)."""
    tokens = batch(EVEN_OUT_BATCH)

    @jax.jit
    def draw(key, tokens):
        params = reference.init_params(key, config, init_std=config["init_std"])
        return reference.even_out_bias(params, tokens, config)

    return functools.partial(draw, tokens=tokens)


def setup(config: dict, traffic: dict, seed: int, comm):
    lm = model(config)
    hyper = config["optimizer"]
    peak, warmup = hyper["lr"], hyper.get("warmup_steps", 0)
    # the first update (count 0) at peak / warmup, the warmup-th at the peak
    schedule = ht.optim.lr_scheduler.LinearLR(peak, 1.0 / warmup, 1.0, warmup - 1) if warmup else peak
    optimizer = ht.optim.DataParallelOptimizer(ht.optim.AdamW(
        lr=schedule, betas=(hyper["b1"], hyper["b2"]), eps=hyper["eps"],
        weight_decay=hyper["weight_decay"], mask=lm.decay_mask))
    dp = ht.nn.DataParallel(lm, comm=comm, optimizer=optimizer)
    batch = _batches(config, traffic, seed)
    draw = _draw(config, batch)
    params = draw(jax.random.key(seed))
    shape_of = lambda tree: jax.tree.map(lambda a: (a.shape, a.dtype), tree)  # noqa: E731
    if shape_of(params) != shape_of(jax.eval_shape(lm.init, jax.random.key(seed))):
        raise ValueError("the reference's parameters are not the model's by name, shape and dtype")
    dp.parameters = params = jax.tree.map(lambda a: comm.shard(a, None), params)
    # the state placed as the step returns it (``kimi_linear_train_step.setup`` says why)
    opt_state = jax.tree.map(lambda a: comm.shard(a, None), optimizer.init_state(params))
    forward = functools.partial(lm.next_token_loss, block_rows=config["loss_block_rows"])
    return types.SimpleNamespace(
        config=config, traffic=traffic, seed=seed, comm=comm, lm=lm, draw=draw,
        params=params, opt_state=opt_state,
        step=dp.make_train_step(_loss, stats=_stats, forward=forward),
        batch=batch,
        steps=0, log=[], tokens_per_step=traffic["sequences"] * traffic["sequence_length"],
        expert_layers=config["num_hidden_layers"] - config["first_k_dense_replace"],
        tally={k: jnp.zeros((), jnp.int32)
               for k in ("moe_rows", "moe_dropped_rows", "moe_fullest_expert_rows")},
    )


def replay(s, steps: int, **lower):
    """The first ``steps`` steps by the plain reference, from the seeded
    initial parameters and the seeded batches, each as a dict: loss, rows, and
    by parameter group the norms of the gradient, of the parameters' change
    and of both moments, the change's product with the parameters and the
    parameters' squares.  ``lower`` is passed to the reference (the controls)."""
    cfg, hyper = s.config, s.config["optimizer"]

    def sequence(params, tokens):
        loss, rows, grads = reference.loss_and_grads(params, tokens[None], cfg, **lower)
        return loss, jnp.stack(rows), grads

    def one(params, adam, tokens):
        # a sequence at a time, the gradients added up: no token of one
        # sequence meets another's
        zero = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype),
                            jax.eval_shape(sequence, params, tokens[0]))
        (loss, rows, grads), _ = jax.lax.scan(
            lambda total, t: (jax.tree.map(jnp.add, total, sequence(params, t)), None), zero, tokens)
        n = tokens.shape[0]
        loss, grads = loss / n, jax.tree.map(lambda g: g / n, grads)
        new, adam = reference.adamw_step(params, grads, adam, **hyper)
        moved = jax.tree.map(jnp.subtract, new, params)
        return new, adam, {
            "loss": loss, "rows": rows, "grad_norms": reference.group_norms(grads),
            "update_norms": reference.group_norms(moved),
            "m_norms": reference.group_norms(adam["m"]), "v_norms": reference.group_norms(adam["v"]),
            "update_dot_params": reference.group_sums(moved, params),
            "params_squared": reference.group_sums(params, params)}

    one = jax.jit(one, donate_argnums=(0, 1))
    params = s.draw(jax.random.key(s.seed))
    adam = reference.adamw_init(params)
    out = []
    for i in range(steps):
        params, adam, facts = one(params, adam, s.batch(i))
        out.append(jax.device_get(facts))
    return out


def compare(s, out, **lower) -> tuple:
    steps = min(s.traffic["check_steps"], len(s.log))
    got = jax.device_get(s.log[:steps])
    dropped = counters(s)["moe_dropped_rows"]
    last_loss, last_rows = float(out[0]), jax.device_get(out[1]["rows"])
    # the replay needs the room the timed path's parameters and moments take
    for leaf in jax.tree.leaves((s.params, s.opt_state)):
        leaf.delete()
    s.params = s.opt_state = None
    want = replay(s, steps, **lower)
    pairs = [(g[1], w) for g, w in zip(got, want)]

    def worst(*kinds):
        # a group the program's tree lacks (the moments hold no selection bias) reads 0
        return _worst((g[kind].get(name, 0.0), w[kind][name])
                      for g, w in pairs for kind in kinds for name in w[kind])

    # the step's product with the parameters, in decays (lr * weight_decay * |p|^2 a
    # group), worst step; the router's few entries go with their experts, as in
    # ``lm_train_step.compare``
    hyper, decay = s.config["optimizer"], {}
    for i, (g, w) in enumerate(pairs if hyper["weight_decay"] else []):
        got_dot, want_dot, squares = (_router_with_experts(d) for d in (
            g["update_dot_params"], w["update_dot_params"], w["params_squared"]))
        for name, square in squares.items():
            err = abs(float(got_dot[name]) - float(want_dot[name])) / (
                _rate(hyper, i + 1) * hyper["weight_decay"] * float(square))
            decay[name] = max(decay.get(name, 0.0), err)
    by_group = lambda kind: {  # noqa: E731  (which group carries a limit's reading)
        name: _worst((g[kind].get(name, 0.0), w[kind][name]) for g, w in pairs) for name in want[0][kind]}
    m_err, v_err = by_group("m_norms"), by_group("v_norms")
    facts = {
        "loss_err": _worst((g[0], w["loss"]) for g, w in zip(got, want)),
        "grad_norm_err": worst("grad_norms"),
        "routed_rows_err": max(
            float(np.abs(g["rows"][layer] - w["rows"][layer]).sum() / max(w["rows"][layer].sum(), 1))
            for g, w in pairs for layer in range(w["rows"].shape[0])),
        "update_err": worst("update_norms"),
        "moment_err": worst("m_norms", "v_norms"),
        "decay_err": max(decay.values(), default=0.0),
        "dropped_rows": dropped,
        "loss_not_finite": int(not np.isfinite(last_loss)),
        "steps_compared": steps,
        "losses": [float(g[0]) for g in got],
        "reference_losses": [float(w["loss"]) for w in want],
        "rows_by_layer_first_steps": [np.asarray(g["rows"]).sum(axis=-1).tolist() for g, _ in pairs],
        "rows_by_layer_last_step": np.asarray(last_rows).sum(axis=-1).tolist(),
        "rows_step0": np.asarray(pairs[0][0]["rows"]).tolist(),
        "reference_rows_step0": np.asarray(want[0]["rows"]).tolist(),
        "last_loss": last_loss,
        "decay_err_by_group": decay,
        "grad_norm_err_by_group": by_group("grad_norms"),
        "update_err_by_group": by_group("update_norms"),
        "moment_err_by_group": {name: max(m_err[name], v_err[name]) for name in m_err},
    }
    for kind in ("grad_norms", "update_norms", "m_norms", "v_norms"):
        facts[f"{kind}_step0"] = {k: float(v) for k, v in pairs[0][0][kind].items()}
        facts[f"reference_{kind}_step0"] = {k: float(v) for k, v in want[0][kind].items()}
    return all(facts[k] <= limit for k, limit in LIMITS.items()), facts


def check(s, out) -> tuple:
    """The first steps' losses, routed rows and, by parameter group, gradient
    norms, parameter steps and moments against the float32 reference's replay."""
    return compare(s, out)


def matmul_parameters(config: dict) -> dict:
    """Parameters that a token multiplies, by kind, from the shapes: a latent
    attention layer's four projections, the dense FFN, one expert, the shared
    SwiGLU (``n_shared_experts`` experts' width), the router, the output head."""
    d, heads, rank = config["hidden_size"], config["num_attention_heads"], config["kv_lora_rank"]
    nope, shared, dv = config["qk_nope_head_dim"], config["qk_rope_head_dim"], config["v_head_dim"]
    return {
        "attention": d * heads * (nope + shared) + d * (rank + shared) + rank * heads * (nope + dv) + heads * dv * d,
        "dense_ffn": 3 * d * config["intermediate_size"],
        "expert": 3 * d * config["moe_intermediate_size"],
        "shared": 3 * d * config["moe_intermediate_size"] * config["n_shared_experts"],
        "router": d * config["num_experts_routed"],
        "head": config["vocab_size"] * d,
    }


def parameters(config: dict) -> int:
    """All parameters held here: the matrices, the embedding, and the vectors
    (two norms and the latent's a layer, the final norm, the selection bias)."""
    p, d, layers = matmul_parameters(config), config["hidden_size"], config["num_hidden_layers"]
    n_dense = config["first_k_dense_replace"]
    expert_layer = (config["n_routed_experts"] * p["expert"] + p["shared"] + p["router"]
                    + config["num_experts_routed"])
    return (layers * (p["attention"] + 2 * d + config["kv_lora_rank"]) + n_dense * p["dense_ffn"]
            + (layers - n_dense) * expert_layer + d + 2 * p["head"])


def work(config: dict, traffic: dict, chips: int) -> dict:
    """Model operations of one step, recomputation not counted: 6 for every
    parameter a token multiplies (forward 2, backward 4), an expert layer's
    routed experts at the expected rows (``tokens x k x held / routed``), and
    attention ``6 (d_qk + d_v)`` for every pair of a query and an earlier key
    (or itself) a head (forward 2 for each of the two products' widths,
    backward twice that), whatever computes them.  ``kernels`` holds what each
    named kernel needs by its shapes alone."""
    p = matmul_parameters(config)
    seqs, length = traffic["sequences"], traffic["sequence_length"]
    tokens = seqs * length
    layers, n_dense = config["num_hidden_layers"], config["first_k_dense_replace"]
    n_expert_layers = layers - n_dense
    d, heads = config["hidden_size"], config["num_attention_heads"]
    d_qk, d_v = config["qk_nope_head_dim"] + config["qk_rope_head_dim"], config["v_head_dim"]
    rows = tokens * config["num_experts_per_tok"] * config["n_routed_experts"] // config["num_experts_routed"]
    per_token = (layers * p["attention"] + n_dense * p["dense_ffn"] + p["head"]
                 + n_expert_layers * (p["router"] + p["shared"]))
    experts_flop = 6 * rows * p["expert"] * n_expert_layers
    attention_flop = 6 * (d_qk + d_v) * attended_pairs(length) * heads * seqs * layers
    act = jnp.dtype(config.get("activation_dtype") or "float32").itemsize
    return {
        "flop": 6 * tokens * per_token + experts_flop + attention_flop,
        # the least a step moves: parameters, gradient and both moments read and written
        "bytes": 28 * parameters(config),
        "derived": {"tokens_per_job": tokens, "steps_per_job": 1},
        "kernels": {
            # ``moe_experts_roofline`` puts the counted rows in the place of the expected
            "moe_experts": {"flop": experts_flop, "scope": "ht.moe.experts",
                            "bytes": n_expert_layers * (config["n_routed_experts"] * p["expert"] * 4
                                                        + rows * 4 * d * act)},
            # q, k (d_qk wide) and v, the output (d_v wide), forward and their cotangents backward
            "flash_attention": {"flop": attention_flop, "scope": "ht.attention",
                                "bytes": layers * tokens * heads * 2 * (2 * d_qk + 2 * d_v) * act},
        },
    }
