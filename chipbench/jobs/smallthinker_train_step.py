"""Job ``smallthinker_train_step``: one training step of a SmallThinker causal
language model (windowed rotary attention beside global attention without
positions, heads of their own width, ReLU-gated experts whose router reads the
layer's input) through ``ht.nn.DataParallel.make_train_step``: forward,
next-token loss, backward and the AdamW update in one jitted program,
parameters and optimizer state donated.  The step, its batches, tallies and
counters are ``lm_train_step``'s and the sums a step reports by parameter
group ``kimi_linear_train_step``'s; the model, the reference
(``references/smallthinker.py``), the limits and the count of work are this
file's.

Configuration keys: the public ``config.json``'s own (``hidden_size``,
``head_dim``, ``moe_ffn_hidden_size``, ``rope_layout``,
``sliding_window_layout`` ..., read by ``model()`` and by the reference),
``moe_num_primary_experts`` being the experts held here; ``layer_types``
(``"global_attention"`` or ``"sliding_attention"`` a layer: the two layouts
spelt out), ``num_experts_routed`` (the router's width), ``experts_held``,
``expert_rows_bound`` (the rows of an expert layer's buffers),
``activation_dtype``, ``init_std``, ``embedding_std`` (the token embedding's
own scale; absent: ``init_std``) and ``optimizer`` (AdamW's ``lr``, ``b1``,
``b2``, ``eps``, ``weight_decay`` and ``warmup_steps``: step ``t`` from 1 uses
``lr * min(1, t / warmup_steps)``).  Traffic keys: ``sequences``,
``sequence_length``, ``zipf_exponent``, ``check_steps``.  The batch of step
``i`` is drawn on the device from ``(seed, i)`` inside the job: token ids Zipf
over the vocabulary, id 0 the most frequent, no padding.

The initial parameters are the reference's draw from ``(seed, configuration)``
(``reference.init_params``), handed to the trainer as a checkpoint would be.
``check`` replays the first ``check_steps`` steps from the same seeded
parameters and batches with the plain float32 reference (dense masked
attention, a loop over the experts held) and its plain AdamW, a sequence at a
time, and compares loss, routed rows and, by parameter group, gradient norms,
the parameters' steps, both moments and the decay with what the timed path
returned; a run that dropped one row of a held expert is not correct.  Its
facts also carry ``flash_blocks``: of each kind of attention layer, how many
grid steps of a head's forward sweep are interior, edge and dead at the run's
shapes (``ops.flash_attention._block_census``).
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
    _batches, _loss, _rate, _router_with_experts, _worst, counters, job)
from chipbench.references import smallthinker as reference
from heat_tpu.nn.models import PatternLM

# The timed path keeps float32 parameters and multiplies bfloat16 operands into
# float32 sums; the reference is float32 throughout.  Each limit lies between the
# largest reading of 12 sound runs (12 seeds) and the readings of four controls on
# one of them: the operands of the reference's products rounded one format below
# bfloat16 (float8_e4m3fn, float8_e5m2), and two of this model's own, the
# reference without its window and with rotary positions on its global layer.  A
# control must fail one limit; the float8 ones and the missing window fail all six,
# rotary positions everywhere five (my chip runs, PR 34; PERF.md has every reading).
LIMITS = {
    # |loss - reference| / reference, worst of the replayed steps: sound 1.2e-5 to
    # 6.5e-5; float8 2.2e-3 and 6.0e-4, no window 2.9e-4, rotary everywhere 4.5e-4
    "loss_err": 1.5e-4,
    # |norm - reference| / reference, worst parameter group (the router in 10 of 12
    # runs, the global layer in 2) and step: sound 5.9e-4 to 4.0e-3; float8 3.7e30
    # twice, no window 3.6e-2, rotary everywhere 2.3e-2
    "grad_norm_err": 1.2e-2,
    # |rows - reference| summed over the experts held / rows routed, worst layer and
    # step (a selection made from bfloat16 operands differs where two logits nearly
    # tie): sound 3.5e-3 to 1.4e-2; float8 0.44 and 0.080, no window 4.2e-2, rotary
    # everywhere 0.10
    "routed_rows_err": 2.5e-2,
    # | |p' - p| - reference's | / reference's, worst parameter group and step: sound
    # 1.6e-4 to 8.1e-4; float8 2.9e27 twice, no window 4.0e-2, rotary everywhere
    # 4.3e-2; a state left unchanged reads 1
    "update_err": 6e-3,
    # the same of AdamW's new moments m and v (v sums fourth powers of the gradient,
    # so a few entries carry it): sound 1.5e-3 to 1.1e-2; float8 9.6e29 twice, no
    # window 6.7e-2 (rotary everywhere reads 4.9e-2, under this limit)
    "moment_err": 5e-2,
    # (p' - p) . p against the reference's in units of lr * weight_decay * |p|^2,
    # worst group (the router with its experts) and step: sound 7.5e-3 to 2.0e-2;
    # float8 11.0 and 2.5, no window 0.25, rotary everywhere 0.36
    "decay_err": 8e-2,
    # rows of held experts that no expert computed, all steps of the run: the
    # buffers (``expert_rows_bound``) hold a row for every token-slot
    "dropped_rows": 0,
    # 1 where the last timed step's loss is not finite
    "loss_not_finite": 0,
}

KINDS = {0: "global_attention", 1: "sliding_attention"}


def model(config: dict) -> PatternLM:
    dtype, kinds = config.get("activation_dtype"), config["layer_types"]
    if kinds != [KINDS[w] for w in config["sliding_window_layout"]]:
        raise ValueError("layer_types is not sliding_window_layout spelt out")
    # a kind of layer is rotated or not as a whole: the two layouts name the same layers
    rotated = {kind for kind, rotary in zip(kinds, config["rope_layout"]) if rotary}
    if rotated & {kind for kind, rotary in zip(kinds, config["rope_layout"]) if not rotary}:
        raise ValueError("rope_layout rotates some layers of a kind and not others")
    lo, hi = config["experts_held"]
    if hi - lo != config["moe_num_primary_experts"] or config["moe_ffn_hidden_size"] != config["moe_intermediate_size"]:
        raise ValueError("an alias in the configuration differs from the published key it stands for")
    return PatternLM(
        config["vocab_size"], config["hidden_size"], kinds,
        num_heads=config["num_attention_heads"], num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], qk_norm=False, window=config["sliding_window_size"],
        rope_kinds=tuple(sorted(rotated)), rope_base=config["rope_theta"],
        ffn_dim=None, num_dense_layers=0, num_experts=config["num_experts_routed"],
        experts_per_token=config["moe_num_active_primary_experts"],
        expert_dim=config["moe_ffn_hidden_size"], experts_held=range(lo, hi),
        router_scoring="softmax", expert_activation="relu", route_before_operator=True,
        norm_topk=config["norm_topk_prob"], expert_rows_bound=config.get("expert_rows_bound"),
        norm_eps=config["rms_norm_eps"], init_std=config["init_std"],
        dtype=None if dtype is None else jnp.dtype(dtype), tie_embedding=config["tie_word_embeddings"])


def _draw(config: dict):
    """``key -> parameters``: the reference's draw, on the device in one program."""
    return jax.jit(functools.partial(reference.init_params, cfg=config, init_std=config["init_std"],
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
    return types.SimpleNamespace(
        config=config, traffic=traffic, seed=seed, comm=comm, lm=lm, draw=draw,
        params=params, opt_state=opt_state,
        step=dp.make_train_step(_loss, stats=_stats), batch=_batches(config, traffic, seed),
        steps=0, log=[], tokens_per_step=traffic["sequences"] * traffic["sequence_length"],
        expert_layers=len(config["layer_types"]),
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
    windows = {"global_attention": None, "sliding_attention": config["sliding_window_size"]}
    return {kind: fa._block_census(-(-length // blk_q) * blk_q, length, blk_q, blk_k, True,
                                   fa._checked_window(windows[kind], True, length))
            for kind in sorted(set(config["layer_types"]))}


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
    attention layer's projections (heads of their own width), one expert, the
    router, the output head."""
    d, heads, kv = config["hidden_size"], config["num_attention_heads"], config["num_key_value_heads"]
    width = config["head_dim"]
    return {
        "attention": d * (heads + 2 * kv) * width + heads * width * d,
        "expert": 3 * d * config["moe_ffn_hidden_size"],
        "router": d * config["num_experts_routed"],
        "head": config["vocab_size"] * d,
    }


def parameters(config: dict) -> int:
    """All parameters held here: the matrices, the embedding and the norms' weights."""
    p, d = matmul_parameters(config), config["hidden_size"]
    layer = p["attention"] + config["moe_num_primary_experts"] * p["expert"] + p["router"] + 2 * d
    return len(config["layer_types"]) * layer + d + 2 * p["head"]


def attended_pairs(length: int, window=None) -> int:
    """Pairs (query, key) of one head and sequence that causal attention
    keeps: ``j <= i`` and, under a window, ``i - j < window``."""
    if window is None or window >= length:
        return length * (length + 1) // 2
    return window * (window + 1) // 2 + (length - window) * window


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
    n_global, n_window = kinds.count("global_attention"), kinds.count("sliding_attention")
    d, heads, kv = config["hidden_size"], config["num_attention_heads"], config["num_key_value_heads"]
    width = config["head_dim"]
    rows = (tokens * config["moe_num_active_primary_experts"] * config["moe_num_primary_experts"]
            // config["num_experts_routed"])
    per_token = len(kinds) * (p["attention"] + p["router"]) + p["head"]
    experts_flop = 6 * rows * p["expert"] * len(kinds)
    a_pair = 6 * 2 * width * heads * seqs
    global_flop = a_pair * attended_pairs(length) * n_global
    window_flop = a_pair * attended_pairs(length, config["sliding_window_size"]) * n_window
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
                            "bytes": len(kinds) * (config["moe_num_primary_experts"] * p["expert"] * 4
                                                   + rows * 4 * d * act)},
            "flash_attention": {"flop": global_flop, "scope": "ht.attention",
                                "bytes": n_global * attention_bytes},
            "window_attention": {"flop": window_flop, "scope": "ht.attention.window",
                                 "bytes": n_window * attention_bytes},
        },
    }
