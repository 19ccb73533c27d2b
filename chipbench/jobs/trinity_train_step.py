"""Job ``trinity_train_step``: one training step of a Trinity (``afmoe``) causal
language model (gated grouped-query attention with normalised heads, windowed
rotary layers beside global layers without positions, a norm before and after
every sublayer, a scaled embedding, sigmoid-routed experts beside a shared one)
through ``ht.nn.DataParallel.make_train_step``: forward, next-token loss,
backward and the AdamW update in one jitted program, parameters and optimizer
state donated.  The loss is ``PatternLM.next_token_loss``'s (the final norm, the
head's product and the log-sum-exp ``loss_block_rows`` rows at a time: no
``(sequence, vocabulary)`` logits), handed to the step as ``forward=``.  The
step's batches, tallies and counters are ``lm_train_step``'s, the sums a step
reports by parameter group ``kimi_linear_train_step``'s and the count of the
pairs attention keeps ``smallthinker_train_step``'s; the model, the reference
(``references/trinity.py``), the limits and the count of work are this file's.

Configuration keys: the public ``config.json``'s own (``hidden_size``,
``head_dim``, ``layer_types``, ``sliding_window``, ``route_scale`` ..., read by
``model()`` and by the reference), ``num_experts`` being the experts held here
and ``num_experts_routed`` the router's width; ``experts_held``,
``expert_rows_bound`` (the hard size of an expert layer's buffers),
``loss_block_rows``, ``activation_dtype``, ``init_std``, ``expert_bias_std``,
``embedding_std`` (the token embedding's own scale; absent: ``init_std``) and
``optimizer`` (AdamW's ``lr``, ``b1``, ``b2``, ``eps``, ``weight_decay`` and
``warmup_steps``: step ``t`` from 1 uses ``lr * min(1, t / warmup_steps)``).
Traffic keys: ``sequences``, ``sequence_length``, ``zipf_exponent``,
``check_steps``.  The batch of step ``i`` is drawn on the device from ``(seed,
i)`` inside the job: token ids Zipf over the vocabulary, id 0 the most
frequent, no padding.

The initial parameters are the reference's draw from ``(seed, configuration)``
(``reference.init_params``), handed to the trainer as a checkpoint would be.
``check`` replays the first ``check_steps`` steps from the same seeded
parameters and batches with the plain float32 reference (dense masked
attention, a loop over the experts held) and its plain AdamW, a sequence at a
time, and compares loss, routed rows and, by parameter group, gradient norms,
the parameters' steps, both moments and the decay with what the timed path
returned; a run that dropped one row of a held expert is not correct.  Its
facts also carry ``flash_blocks`` (of each kind of attention layer, how many
grid steps of a head's forward sweep are interior, edge and dead at the run's
shapes: ``ops.flash_attention._block_census``) and ``head_blocks`` (the blocks
of rows the head and loss are computed in).
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
from chipbench.references import trinity as reference
from heat_tpu.nn.models import PatternLM

# The timed path keeps float32 parameters and multiplies bfloat16 operands into
# float32 sums; the reference is float32 throughout.  Each limit lies between the
# largest reading of 16 sound runs (16 seeds) and the readings of two controls on
# one of them that round the operands of the reference's products one format below
# bfloat16 (float8_e4m3fn, float8_e5m2), given in that order; three controls of
# this model's own (the reference without its gate, without its window, with its
# embedding unscaled, in that order) must fail a limit each.  The float8 controls
# and the unscaled embedding fail all six, no window five (all but
# ``grad_norm_err``), no gate four (my chip runs, PR 38; PERF.md has every reading).
LIMITS = {
    # |loss - reference| / reference, worst of the replayed steps: sound 1.8e-5 to
    # 9.9e-5; float8 2.4e-3 and 1.7e-3; the model's own 1.4e-3, 1.6e-3, 3.3e-3
    "loss_err": 3e-4,
    # |norm - reference| / reference, worst parameter group (the router in all 16:
    # its gradient comes from the rows the held experts got, 1,094 to 66,181 a
    # layer, and every other group reads under 8e-3) and step: sound 4.3e-3 to
    # 5.8e-2; float8 23.2 and 23.5; the model's own 0.086, 0.18, 0.47
    "grad_norm_err": 0.2,
    # |rows - reference| summed over the experts held / rows routed, worst layer and
    # step (a selection made from bfloat16 operands differs where two scores nearly
    # tie; the largest reading is of a layer whose held experts got 1,094 rows): sound
    # 9.4e-3 to 2.4e-2; float8 0.86 and 0.72; the model's own 0.76, 0.57, 0.49
    "routed_rows_err": 0.1,
    # | |p' - p| - reference's | / reference's, worst parameter group and step: sound
    # 9.8e-4 to 5.3e-3; float8 26.0 and 0.80; the model's own 0.21, 0.069, 0.086; a
    # state left unchanged reads 1
    "update_err": 2.5e-2,
    # the same of AdamW's new moments m and v (v sums fourth powers of the gradient,
    # so a few entries carry it; the router's in every run): sound 2.9e-3 to 0.11;
    # float8 649 and 671; the model's own 0.27, 0.42, 0.75
    "moment_err": 0.4,
    # (p' - p) . p against the reference's in units of lr * weight_decay * |p|^2,
    # worst group (the router with its experts) and step: sound 1.4e-2 to 4.6e-2;
    # float8 2.5 and 1.0; the model's own 0.70, 1.07, 1.67
    "decay_err": 0.2,
    # rows of held experts that no expert computed, all steps of the run: the
    # buffers' hard size (``expert_rows_bound``) holds a row for every token-slot
    "dropped_rows": 0,
    # 1 where the last timed step's loss is not finite
    "loss_not_finite": 0,
}

WINDOWED, GLOBAL = "sliding_attention", "full_attention"


def model(config: dict) -> PatternLM:
    dtype = config.get("activation_dtype")
    lo, hi = config["experts_held"]
    if hi - lo != config["num_experts"] or len(config["layer_types"]) != config["num_hidden_layers"]:
        raise ValueError("num_experts is not the experts held, or layer_types not a layer each")
    return PatternLM(
        config["vocab_size"], config["hidden_size"], config["layer_types"],
        num_heads=config["num_attention_heads"], num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], qk_norm=True, window=config["sliding_window"],
        rope_kinds=(WINDOWED,), rope_base=config["rope_theta"],
        ffn_dim=config["intermediate_size"], num_dense_layers=config["num_dense_layers"],
        num_experts=config["num_experts_routed"], experts_per_token=config["num_experts_per_tok"],
        expert_dim=config["moe_intermediate_size"], experts_held=range(lo, hi),
        routed_scaling=config["route_scale"], norm_topk=config["route_norm"],
        shared_expert_dim=config["num_shared_experts"] * config["moe_intermediate_size"],
        expert_rows_bound=config.get("expert_rows_bound"), norm_eps=config["rms_norm_eps"],
        init_std=config["init_std"], bias_std=config["expert_bias_std"],
        dtype=None if dtype is None else jnp.dtype(dtype), tie_embedding=config["tie_word_embeddings"],
        attention_gate=True, output_norms=True,
        embedding_scale=config["hidden_size"] ** 0.5 if config["mup_enabled"] else None)


def _loss(out, tokens):
    """``forward=`` already returned ``(loss, routing)``."""
    return out


def _draw(config: dict):
    """``key -> parameters``: the reference's draw, on the device in one program."""
    return jax.jit(functools.partial(reference.init_params, cfg=config, init_std=config["init_std"],
                                     bias_std=config["expert_bias_std"],
                                     embed_std=config.get("embedding_std")))


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
    # the state placed as the step returns it (``kimi_linear_train_step.setup`` says why)
    opt_state = jax.tree.map(lambda a: comm.shard(a, None), optimizer.init_state(params))
    forward = functools.partial(lm.next_token_loss, block_rows=config["loss_block_rows"])
    return types.SimpleNamespace(
        config=config, traffic=traffic, seed=seed, comm=comm, lm=lm, draw=draw,
        params=params, opt_state=opt_state,
        step=dp.make_train_step(_loss, stats=_stats, forward=forward),
        batch=_batches(config, traffic, seed),
        steps=0, log=[], tokens_per_step=traffic["sequences"] * traffic["sequence_length"],
        expert_layers=len(config["layer_types"]) - config["num_dense_layers"],
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


def flash_blocks(config: dict, traffic: dict) -> dict:
    """Of each kind of attention layer here: how many steps of one head's
    forward sweep are interior, edge and dead at these shapes."""
    import importlib

    fa = importlib.import_module("heat_tpu.ops.flash_attention")  # the attribute of ``ops`` is the function
    length = traffic["sequence_length"]
    act = jnp.dtype(config.get("activation_dtype") or "float32").itemsize
    blk_q, blk_k = fa._block_shape(length, config["head_dim"], act)
    windows = {GLOBAL: None, WINDOWED: config["sliding_window"]}
    return {kind: fa._block_census(-(-length // blk_q) * blk_q, length, blk_q, blk_k, True,
                                   fa._checked_window(windows[kind], True, length))
            for kind in sorted(set(config["layer_types"]))}


def head_blocks(config: dict, traffic: dict) -> int:
    """The blocks of rows a step's head and loss are computed in."""
    rows = traffic["sequences"] * traffic["sequence_length"]
    return -(-rows // min(config["loss_block_rows"], rows))


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
        "flash_blocks": flash_blocks(s.config, s.traffic),
        "head_blocks": head_blocks(s.config, s.traffic),
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
    """Parameters that a token multiplies, by kind, from the shapes: an
    attention layer's projections (heads of their own width) with its gate,
    the dense FFN, one expert (the shared one has the same shape), the router,
    the output head."""
    d, heads, kv = config["hidden_size"], config["num_attention_heads"], config["num_key_value_heads"]
    width = config["head_dim"]
    return {
        "attention": d * (heads + 2 * kv) * width + heads * width * d,
        "gate": d * heads * width,
        "dense_ffn": 3 * d * config["intermediate_size"],
        "expert": 3 * d * config["moe_intermediate_size"],
        "router": d * config["num_experts_routed"],
        "head": config["vocab_size"] * d,
    }


def parameters(config: dict) -> int:
    """All parameters held here: the matrices, the embedding, and the vectors
    (four norms a layer, the QK norms, the final norm, the selection bias)."""
    p, d, kinds = matmul_parameters(config), config["hidden_size"], config["layer_types"]
    n_dense = config["num_dense_layers"]
    expert_layer = ((config["num_experts"] + config["num_shared_experts"]) * p["expert"]
                    + p["router"] + config["num_experts_routed"])
    return (len(kinds) * (p["attention"] + p["gate"] + 2 * config["head_dim"] + 4 * d)
            + n_dense * p["dense_ffn"] + (len(kinds) - n_dense) * expert_layer + d + 2 * p["head"])


def work(config: dict, traffic: dict, chips: int) -> dict:
    """Model operations of one step, recomputation not counted: 6 for every
    parameter a token multiplies (forward 2, backward 4), an expert layer's
    routed experts at the expected rows (``tokens x k x held / routed``), and
    attention ``6 (d_qk + d_v)`` for every pair of a query and a key it keeps
    (forward 2 for each of the two products' widths, backward twice that):
    the causal triangle in a global layer, only the pairs inside the window in
    a windowed one, whatever computes them.  ``kernels`` holds what each named
    kernel needs by its shapes alone."""
    p = matmul_parameters(config)
    seqs, length = traffic["sequences"], traffic["sequence_length"]
    tokens = seqs * length
    kinds = config["layer_types"]
    n_dense = config["num_dense_layers"]
    n_expert_layers = len(kinds) - n_dense
    n_global, n_window = kinds.count(GLOBAL), kinds.count(WINDOWED)
    d, heads, kv = config["hidden_size"], config["num_attention_heads"], config["num_key_value_heads"]
    width = config["head_dim"]
    rows = tokens * config["num_experts_per_tok"] * config["num_experts"] // config["num_experts_routed"]
    per_token = (len(kinds) * (p["attention"] + p["gate"]) + n_dense * p["dense_ffn"] + p["head"]
                 + n_expert_layers * (p["router"] + config["num_shared_experts"] * p["expert"]))
    experts_flop = 6 * rows * p["expert"] * n_expert_layers
    a_pair = 6 * 2 * width * heads * seqs
    global_flop = a_pair * attended_pairs(length) * n_global
    window_flop = a_pair * attended_pairs(length, config["sliding_window"]) * n_window
    act = jnp.dtype(config.get("activation_dtype") or "float32").itemsize
    # q and the output a query head, k and v a key/value head, forward and their cotangents backward
    attention_bytes = tokens * 2 * 2 * width * (heads + kv) * act
    return {
        "flop": 6 * tokens * per_token + experts_flop + global_flop + window_flop,
        # the least a step moves: parameters, gradient and both moments read and written
        "bytes": 28 * parameters(config),
        "derived": {"tokens_per_job": tokens, "steps_per_job": 1},
        "kernels": {
            # ``moe_experts_roofline`` puts the counted rows in the place of the expected
            "moe_experts": {"flop": experts_flop, "scope": "ht.moe.experts",
                            "bytes": n_expert_layers * (config["num_experts"] * p["expert"] * 4
                                                        + rows * 4 * d * act)},
            "flash_attention": {"flop": global_flop, "scope": "ht.attention",
                                "bytes": n_global * attention_bytes},
            "window_attention": {"flop": window_flop, "scope": "ht.attention.window",
                                 "bytes": n_window * attention_bytes},
        },
    }
