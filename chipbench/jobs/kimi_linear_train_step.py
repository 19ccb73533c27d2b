"""Job ``kimi_linear_train_step``: one training step of a Kimi Linear causal
language model (Kimi Delta Attention and latent attention layers, experts
beside a shared one) through ``ht.nn.DataParallel.make_train_step``: forward,
next-token loss, backward and the AdamW update in one jitted program,
parameters and optimizer state donated.  The step, its batches, tallies and
counters are ``lm_train_step``'s; the model, the reference
(``references/kimi_linear.py``), the parameter groups, the limits and the
count of work are this file's.

Configuration keys: the public ``config.json``'s own (``hidden_size``,
``linear_attn_config``, ``kv_lora_rank``, ``qk_nope_head_dim`` ..., read by
``model()``), ``num_experts`` being the experts held here and
``num_experts_routed`` the router's width; ``layer_types`` (``"kda"`` or
``"mla"`` a layer, in the order of the layers kept), ``experts_held``,
``expert_rows_bound`` (the rows of an expert layer's buffers),
``kda_gate_rank``, ``kda_chunk``, ``activation_dtype``, ``init_std``,
``expert_bias_std`` and ``optimizer`` (AdamW's ``lr``, ``b1``, ``b2``, ``eps``,
``weight_decay`` and ``warmup_steps``: step ``t`` from 1 uses ``lr * min(1, t /
warmup_steps)``).  Traffic keys: ``sequences``, ``sequence_length``,
``zipf_exponent``, ``check_steps``.  The batch of step ``i`` is drawn on the
device from ``(seed, i)`` inside the job: token ids Zipf over the vocabulary,
id 0 the most frequent, no padding.

The initial parameters are the reference's draw from ``(seed, configuration)``
(``reference.init_params``), handed to the trainer as a checkpoint would be.
``check`` replays the first ``check_steps`` steps from the same seeded
parameters and batches with the plain float32 reference (Kimi Delta Attention
as its token recurrence) and its plain AdamW, a sequence at a time, and
compares loss, routed rows and, by parameter group, gradient norms, the
parameters' steps, both moments and the decay with what the timed path
returned; a run that dropped one row of a held expert is not correct.
"""

from __future__ import annotations

import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import optax

import heat_tpu as ht
from chipbench.jobs.lm_train_step import (  # noqa: F401  (job and counters are this job kind's too)
    _batches, _loss, _rate, _router_with_experts, _tally, _worst, counters, job)
from chipbench.references import kimi_linear as reference
from heat_tpu.nn.models import PatternLM

# The timed path keeps float32 parameters and multiplies bfloat16 operands into
# float32 sums; the reference is float32 throughout.  Each limit lies between the
# largest reading of the sound runs and the readings of two controls that round
# the operands of the reference's products one format below bfloat16
# (float8_e4m3fn, float8_e5m2); a control must fail one of them, and these fail
# five of the six (my chip runs, PR 32; PERF.md has every reading).
LIMITS = {
    # |loss - reference| / reference, worst of the replayed steps: sound (15 seeds)
    # 1.2e-5 to 4.3e-5, controls 2.4e-4 and 3.4e-4
    "loss_err": 1e-4,
    # |norm - reference| / reference, worst parameter group and step: sound 1.2e-3
    # to 5.4e-3 (worst in the router; a KDA operator reads 5e-5 to 3.4e-4), controls 451 and 1.8e30
    "grad_norm_err": 5e-2,
    # |rows - reference| summed over the experts held / rows routed, worst layer and
    # step (a selection made from bfloat16 activations differs where two scores
    # nearly tie, and the 8th and 9th of a token's 256 scores lie 0.004 apart):
    # sound 6.6e-3 to 1.5e-2, controls 3.7e-2 and 4.0e-2
    "routed_rows_err": 2.5e-2,
    # | |p' - p| - reference's | / reference's, worst parameter group and step:
    # sound 3.3e-4 to 7.3e-3, controls 13 and 2.2e27; a state left unchanged reads 1
    "update_err": 0.1,
    # the same of AdamW's new moments m and v: sound 5.0e-3 to 9.2e-3, controls 9.4e4
    # and 3.0e29
    "moment_err": 0.25,
    # (p' - p) . p against the reference's in units of lr * weight_decay * |p|^2,
    # worst group (the router with its experts) and step: sound 1.7e-2 to 5.9e-2,
    # controls 3.1 and 9.7
    "decay_err": 0.3,
    # rows of held experts that no expert computed, all steps of the run: the
    # buffers' bound (``expert_rows_bound``) is no capacity factor
    "dropped_rows": 0,
    # 1 where the last timed step's loss is not finite
    "loss_not_finite": 0,
}


def model(config: dict) -> PatternLM:
    dtype, lin = config.get("activation_dtype"), config["linear_attn_config"]
    return PatternLM(
        config["vocab_size"], config["hidden_size"], config["layer_types"],
        num_heads=config["num_attention_heads"], ffn_dim=config["intermediate_size"],
        num_dense_layers=config["first_k_dense_replace"], num_experts=config["num_experts_routed"],
        experts_per_token=config["num_experts_per_token"], expert_dim=config["moe_intermediate_size"],
        experts_held=range(*config["experts_held"]), routed_scaling=config["routed_scaling_factor"],
        norm_topk=config["moe_renormalize"], conv_taps=lin["short_conv_kernel_size"],
        norm_eps=config["rms_norm_eps"], init_std=config["init_std"], bias_std=config["expert_bias_std"],
        dtype=None if dtype is None else jnp.dtype(dtype), tie_embedding=config["tie_word_embeddings"],
        shared_expert_dim=config["num_shared_experts"] * config["moe_intermediate_size"],
        expert_rows_bound=config.get("expert_rows_bound"), kda_heads=lin["num_heads"],
        kda_head_dim=lin["head_dim"], kda_gate_rank=config["kda_gate_rank"],
        kda_chunk=config.get("kda_chunk", 64), kv_rank=config["kv_lora_rank"],
        qk_nope_dim=config["qk_nope_head_dim"], qk_shared_dim=config["qk_rope_head_dim"],
        v_dim=config["v_head_dim"])


def reference_config(config: dict) -> dict:
    """The configuration as the reference reads it: the router's width under
    the reference's name for it."""
    return {**config, "num_experts": config["num_experts_routed"]}


def _group(path) -> str:
    """The parameter group of a leaf of ``PatternLM``'s parameters, read off
    the program's own tree (the reference has its reading, ``group_of``)."""
    names = [str(getattr(k, "key", getattr(k, "idx", ""))) for k in path]
    leaf = names[-2] if names[-1] == "weight" else names[-1]
    if names[0] in ("embed", "head"):
        return "embedding" if names[0] == "embed" else "head"
    if leaf.endswith("norm"):
        return "norms"
    if leaf == "router":
        return "router"
    if leaf == "expert_bias":
        return "selection_bias"
    if names[2] == "operator":
        return f"operator_{names[1]}"
    if names[3] == "shared":
        return "shared_expert"
    return "dense_ffn" if names[-1] == "weight" else "experts"  # an expert's matrices are stacked, bare


def _by_group(leafwise, *trees) -> dict:
    """``leafwise(*leaves)`` summed over each parameter group's leaves."""
    sums = {}
    flat = [jax.tree_util.tree_flatten_with_path(t)[0] for t in trees]
    for leaves in zip(*flat):
        name = _group(leaves[0][0])
        sums[name] = sums.get(name, 0.0) + leafwise(*(a.astype(jnp.float32) for _, a in leaves))
    return sums


def _norms(tree) -> dict:
    return {k: jnp.sqrt(v) for k, v in _by_group(lambda a: jnp.sum(a * a), tree).items()}


def _stats(grads, routing, params, new_params, new_state):
    # as ``lm_train_step._stats``: the sums fuse into the update's own passes
    with jax.named_scope("ht.optim.update"):
        moved = jax.tree.map(jnp.subtract, new_params, params)
        update = {"update_norms": _norms(moved),
                  "m_norms": _norms(optax.tree_utils.tree_get(new_state, "mu")),
                  "v_norms": _norms(optax.tree_utils.tree_get(new_state, "nu")),
                  "update_dot_params": _by_group(lambda d, a: jnp.sum(d * a), moved, params)}
    return {"grad_norms": _norms(grads), **update,
            "rows": jnp.stack([r["rows"] for r in routing]),
            "dropped": sum(r["dropped"] for r in routing)}


def _draw(config: dict):
    """``key -> parameters``: the reference's draw, on the device in one program."""
    return jax.jit(functools.partial(reference.init_params, cfg=reference_config(config),
                                     init_std=config["init_std"], bias_std=config["expert_bias_std"]))


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
    draw = _draw(config)
    params = draw(jax.random.key(seed))
    shape_of = lambda tree: jax.tree.map(lambda a: (a.shape, a.dtype), tree)  # noqa: E731
    if shape_of(params) != shape_of(jax.eval_shape(lm.init, jax.random.key(seed))):
        raise ValueError("the reference's parameters are not the model's by name, shape and dtype")
    dp.parameters = params = jax.tree.map(lambda a: comm.shard(a, None), params)
    # the state placed as the step returns it: left as ``init_state`` makes it (its
    # count uncommitted), the second step is another program and compiles again
    opt_state = jax.tree.map(lambda a: comm.shard(a, None), optimizer.init_state(params))
    return types.SimpleNamespace(
        config=config, traffic=traffic, seed=seed, comm=comm, lm=lm, draw=draw,
        params=params, opt_state=opt_state,
        step=dp.make_train_step(_loss, stats=_stats), batch=_batches(config, traffic, seed),
        steps=0, log=[], tokens_per_step=traffic["sequences"] * traffic["sequence_length"],
        expert_layers=len(config["layer_types"]) - config["first_k_dense_replace"],
        tally={k: jnp.zeros((), jnp.int32)
               for k in ("moe_rows", "moe_dropped_rows", "moe_fullest_expert_rows")},
    )


def replay(s, steps: int, **lower):
    """The first ``steps`` steps by the plain reference, from the seeded
    initial parameters and the seeded batches, each as a dict: loss, rows, and
    by parameter group the norms of the gradient, of the parameters' change
    and of both moments, the change's product with the parameters and the
    parameters' squares.  ``lower`` is passed to the reference (the controls)."""
    cfg, hyper = reference_config(s.config), s.config["optimizer"]

    def sequence(params, tokens):
        loss, rows, grads = reference.loss_and_grads(params, tokens[None], cfg, **lower)
        return loss, jnp.stack(rows), grads

    def one(params, adam, tokens):
        # a sequence at a time, the gradients added up: no token of one
        # sequence meets another's, and a whole batch in float32 does not fit
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
    """Parameters that a token multiplies, by kind, from the shapes: the two
    operators' projections (Kimi Delta Attention's with its low-rank gates and
    beta), the dense FFN, one expert (the shared one has the same shape), the
    router, the output head."""
    d, heads, lin = config["hidden_size"], config["num_attention_heads"], config["linear_attn_config"]
    width, rank = lin["num_heads"] * lin["head_dim"], config["kda_gate_rank"]
    nope, shared, dv = config["qk_nope_head_dim"], config["qk_rope_head_dim"], config["v_head_dim"]
    return {
        "kda": 3 * d * width + width * d + 2 * (d * rank + rank * width) + d * lin["num_heads"],
        "mla": (d * heads * (nope + shared) + d * (config["kv_lora_rank"] + shared)
                + config["kv_lora_rank"] * heads * (nope + dv) + heads * dv * d),
        "dense_ffn": 3 * d * config["intermediate_size"],
        "expert": 3 * d * config["moe_intermediate_size"],
        "router": d * config["num_experts_routed"],
        "head": config["vocab_size"] * d,
    }


def parameters(config: dict) -> int:
    """All parameters held here: the matrices, the embedding, and the vectors
    (convolution taps, ``A_log``, ``dt_bias``, norm weights, the selection bias)."""
    p, kinds = matmul_parameters(config), config["layer_types"]
    d, lin = config["hidden_size"], config["linear_attn_config"]
    width = lin["num_heads"] * lin["head_dim"]
    kda = p["kda"] + 3 * width * lin["short_conv_kernel_size"] + width + lin["num_heads"] + lin["head_dim"]
    n_dense = config["first_k_dense_replace"]
    expert_layer = ((config["num_experts"] + config["num_shared_experts"]) * p["expert"]
                    + p["router"] + config["num_experts_routed"])
    return (sum(kda if k == "kda" else p["mla"] + config["kv_lora_rank"] for k in kinds)
            + n_dense * p["dense_ffn"] + (len(kinds) - n_dense) * expert_layer
            + 2 * len(kinds) * d + d + 2 * p["head"])


def work(config: dict, traffic: dict, chips: int) -> dict:
    """Model operations of one step, recomputation not counted: 6 for every
    parameter a token multiplies (forward 2, backward 4), an expert layer's
    routed experts at the expected rows (``tokens x k x held / routed``),
    causal attention ``3 S^2 (d_qk + d_v)`` a head and sequence (forward ``S^2``
    for each of the two products' widths, backward twice that), and Kimi Delta
    Attention's recurrence ``21 d^2`` a token and head (forward 7: the decay of
    the state, ``k^T S``, the rank-one update and the read-out; backward twice
    that), whatever computes it.  ``kernels`` holds what each named kernel
    needs by its shapes alone."""
    p = matmul_parameters(config)
    seqs, length = traffic["sequences"], traffic["sequence_length"]
    tokens = seqs * length
    kinds, lin = config["layer_types"], config["linear_attn_config"]
    n_dense = config["first_k_dense_replace"]
    n_expert_layers = len(kinds) - n_dense
    n_kda, n_mla = kinds.count("kda"), kinds.count("mla")
    d, heads = config["hidden_size"], config["num_attention_heads"]
    d_qk, d_v = config["qk_nope_head_dim"] + config["qk_rope_head_dim"], config["v_head_dim"]
    rows = tokens * config["num_experts_per_token"] * config["num_experts"] // config["num_experts_routed"]
    per_token = (n_kda * p["kda"] + n_mla * p["mla"] + n_dense * p["dense_ffn"] + p["head"]
                 + n_expert_layers * (p["router"] + config["num_shared_experts"] * p["expert"]))
    experts_flop = 6 * rows * p["expert"] * n_expert_layers
    attention_flop = 3 * length * length * (d_qk + d_v) * heads * seqs * n_mla
    kda_flop = 21 * lin["head_dim"] ** 2 * lin["num_heads"] * tokens * n_kda
    act = jnp.dtype(config.get("activation_dtype") or "float32").itemsize
    return {
        "flop": 6 * tokens * per_token + experts_flop + attention_flop + kda_flop,
        # the least a step moves: parameters, gradient and both moments read and written
        "bytes": 28 * parameters(config),
        "derived": {"tokens_per_job": tokens, "steps_per_job": 1},
        "kernels": {
            # ``moe_experts_roofline`` puts the counted rows in the place of the expected
            "moe_experts": {"flop": experts_flop, "scope": "ht.moe.experts",
                            "bytes": n_expert_layers * (config["num_experts"] * p["expert"] * 4
                                                        + rows * 4 * d * act)},
            # q, k (d_qk wide) and v, the output (d_v wide), forward and their cotangents backward
            "flash_attention": {"flop": attention_flop, "scope": "ht.attention",
                                "bytes": n_mla * tokens * heads * 2 * (2 * d_qk + 2 * d_v) * act},
            # q, k, v, the output (the activations' dtype), the log-decay a channel and beta
            # (float32), once each forward and their cotangents once each backward: memory-bound
            "kda": {"flop": kda_flop, "scope": "ht.kda",
                    "bytes": n_kda * tokens * lin["num_heads"] * 2 * (
                        4 * lin["head_dim"] * act + (lin["head_dim"] + 1) * 4)},
        },
    }
