"""Job ``lm_train_step``: one training step of a causal language model through
``ht.nn.DataParallel.make_train_step``: forward, next-token loss, backward and
the AdamW update in one jitted program, parameters and optimizer state donated.

Configuration keys: a public ``config.json``'s own (``hidden_size``,
``layer_types``, ``num_experts`` ..., read by ``model()``), ``num_experts``
being the experts held here and ``num_experts_routed`` the router's width,
``experts_held``, ``activation_dtype``, ``init_std``, ``expert_bias_std`` and
``optimizer`` (AdamW's ``lr``, ``b1``, ``b2``, ``eps``, ``weight_decay`` and
``warmup_steps``: step ``t`` from 1 uses ``lr * min(1, t / warmup_steps)``).  Traffic keys: ``sequences``, ``sequence_length``,
``zipf_exponent``, ``check_steps``.  The batch of step ``i`` is drawn on the
device from ``(seed, i)`` inside the job (one small program): token ids Zipf
over the vocabulary, id 0 the most frequent, no padding.

The initial parameters are the reference's draw from ``(seed, configuration)``
(``reference.init_params``), handed to the trainer as a checkpoint would be:
the program's own initialiser only has to agree on names and shapes.

Every step's loss, the rows routed to each expert held and, by parameter group,
the norms of the gradient, of the step the parameters took, of AdamW's two new
moments and the step's product with the parameters (where a decay shows) come
out of the step's own program (the ``stats=`` hook) and stay on the device,
where a third small program adds the rows to the run's tallies (``counters``
fetches those).  ``check`` replays the first ``check_steps`` steps (the warm-up
steps and the first timed one) from the same seeded parameters and batches with
the plain float32 reference and its plain AdamW and compares them with what
the timed path returned.
"""

from __future__ import annotations

import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import optax

import heat_tpu as ht
from chipbench.references import lfm2_moe as reference
from heat_tpu.nn.models import PatternLM

# The timed path keeps float32 parameters and multiplies bfloat16 operands into
# float32 sums; the reference is float32 throughout.  The first three limits lie
# between the largest reading of 45 sound runs (45 seeds) and the readings of three
# controls that round the operands of the reference's products one format lower
# (float8_e5m2); a control must fail one of them, and these fail all three.  The
# next three hold the optimizer: they lie between the 23 sound runs that had them
# and what a fault planted in the timed program's optimizer reads at the cell's
# size (my chip runs, PR 28; PERF.md has every reading).
LIMITS = {
    # |loss - reference| / reference, worst of the replayed steps: sound 0.3e-5 to
    # 5.1e-5, controls 2.6e-4 to 4.3e-4; a state left unchanged reads 3.2e-4 and 3.4e-4
    "loss_err": 1.5e-4,
    # |norm - reference| / reference, worst parameter group and step:
    # sound 1.1e-3 to 8.5e-3, controls 171 to 206
    "grad_norm_err": 5e-2,
    # |rows - reference| summed over the experts held / rows routed, worst layer and
    # step (a selection made from bfloat16 activations differs where two scores
    # nearly tie): sound 2.0e-3 to 5.7e-3, controls 3.8e-2 to 4.4e-2
    "routed_rows_err": 1.5e-2,
    # | |p' - p| - reference's | / reference's: the step the parameters took in the
    # timed program against the reference's AdamW, worst parameter group and step:
    # sound 3.3e-4 to 2.0e-3; a state left unchanged reads 1 (twice), every leaf
    # decaying 1.4e-2, b1 0.8 for 0.9 0.9e-2, the control 4.1
    "update_err": 0.1,
    # the same of AdamW's new moments m and v: sound 4.4e-3 to 3.4e-2 (v sums fourth
    # powers of the gradient, so a few entries carry it); b1 0.8 for 0.9 reads 1.0,
    # b2 0.999 for 0.95 reads 0.98
    "moment_err": 0.25,
    # (p' - p) . p against the reference's in units of lr * weight_decay * |p|^2, worst
    # group (the router with its experts) and step: sound 2.4e-2 to 5.6e-2; every
    # leaf decaying (norms and embedding too) reads 1.09 and 1.10, none decaying 1.12
    "decay_err": 0.3,
    # rows of held experts that no expert computed, all steps of the run
    "dropped_rows": 0,
    # 1 where the last timed step's loss is not finite
    "loss_not_finite": 0,
}


def model(config: dict) -> PatternLM:
    dtype = config.get("activation_dtype")
    return PatternLM(
        config["vocab_size"], config["hidden_size"], config["layer_types"],
        num_heads=config["num_attention_heads"], num_kv_heads=config["num_key_value_heads"],
        ffn_dim=config["intermediate_size"], num_dense_layers=config["num_dense_layers"],
        num_experts=config["num_experts_routed"], experts_per_token=config["num_experts_per_tok"],
        expert_dim=config["moe_intermediate_size"], experts_held=range(*config["experts_held"]),
        routed_scaling=config["routed_scaling_factor"], norm_topk=config["norm_topk_prob"],
        conv_taps=config["conv_L_cache"], rope_base=config["rope_theta"],
        norm_eps=config["norm_eps"], init_std=config["init_std"],
        bias_std=config["expert_bias_std"], dtype=None if dtype is None else jnp.dtype(dtype))


def reference_config(config: dict) -> dict:
    """The configuration as the reference reads it: the router's width under
    the reference's name for it."""
    return {**config, "num_experts": config["num_experts_routed"]}


def _batches(config: dict, traffic: dict, seed: int):
    """``step index -> tokens (sequences, length)``, one jitted program."""
    vocab, exponent = config["vocab_size"], traffic["zipf_exponent"]
    shape = (traffic["sequences"], traffic["sequence_length"])
    cdf = np.cumsum(np.arange(1, vocab + 1, dtype=np.float64) ** -exponent)
    cdf = jnp.asarray(cdf / cdf[-1], jnp.float32)
    key = jax.random.key(seed)

    @jax.jit
    def batch(i):
        u = jax.random.uniform(jax.random.fold_in(key, i), shape)
        return jnp.minimum(jnp.searchsorted(cdf, u), vocab - 1).astype(jnp.int32)

    return batch


def _loss(out, tokens):
    logits, routing = out
    return ht.nn.losses.next_token_cross_entropy(logits, tokens), routing


def _group(path) -> str:
    """The parameter group of a leaf of ``PatternLM``'s parameters, read off
    the program's own tree (the reference has its reading, ``group_of``)."""
    names = [str(getattr(k, "key", getattr(k, "idx", ""))) for k in path]
    leaf = names[-2] if names[-1] == "weight" else names[-1]
    if names[0] == "embed":
        return "embedding"
    if leaf.endswith("norm"):
        return "norms"
    if leaf == "router":
        return "router"
    if leaf == "expert_bias":
        return "selection_bias"
    if names[2] == "operator":
        return f"operator_{names[1]}"
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
    # XLA fuses these sums into the update's own passes over the parameters and
    # moments (they cost no time of their own: PERF.md, PR 28); the scope keeps
    # those fusions under the update's name, where ``optimizer_ms`` reads them
    with jax.named_scope("ht.optim.update"):
        moved = jax.tree.map(jnp.subtract, new_params, params)
        update = {"update_norms": _norms(moved),
                  "m_norms": _norms(optax.tree_utils.tree_get(new_state, "mu")),
                  "v_norms": _norms(optax.tree_utils.tree_get(new_state, "nu")),
                  # a decoupled decay is the part of the step along the parameters
                  "update_dot_params": _by_group(lambda d, a: jnp.sum(d * a), moved, params)}
    return {"grad_norms": _norms(grads), **update,
            "rows": jnp.stack([r["rows"] for r in routing]),
            "dropped": sum(r["dropped"] for r in routing)}


@jax.jit
def _tally(tally, rows, dropped):
    """The run's tallies plus one step's: rows routed to the experts held,
    rows dropped, and each expert layer's fullest expert's rows."""
    return {"moe_rows": tally["moe_rows"] + jnp.sum(rows),
            "moe_dropped_rows": tally["moe_dropped_rows"] + dropped,
            "moe_fullest_expert_rows": tally["moe_fullest_expert_rows"] + jnp.sum(jnp.max(rows, axis=-1))}


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
    return types.SimpleNamespace(
        config=config, traffic=traffic, seed=seed, comm=comm, lm=lm, draw=draw,
        params=params, opt_state=optimizer.init_state(params),
        step=dp.make_train_step(_loss, stats=_stats), batch=_batches(config, traffic, seed),
        steps=0, log=[], tokens_per_step=traffic["sequences"] * traffic["sequence_length"],
        expert_layers=len(config["layer_types"]) - config["num_dense_layers"],
        tally={k: jnp.zeros((), jnp.int32)
               for k in ("moe_rows", "moe_dropped_rows", "moe_fullest_expert_rows")},
    )


def job(s):
    tokens = s.batch(s.steps)
    with jax.profiler.TraceAnnotation("ht.nn.DataParallel.train_step"):
        s.params, s.opt_state, loss, stats = s.step(s.params, s.opt_state, tokens, tokens)
    s.steps += 1
    s.log.append((loss, stats))
    s.tally = _tally(s.tally, stats["rows"], stats["dropped"])
    return loss, stats


def counters(s) -> dict:
    """Tallies over all steps so far: tokens, expert layers run, and from the
    device the rows routed to the experts held, the rows dropped and the
    fullest held expert's rows (summed over steps and expert layers)."""
    return {"tokens": s.steps * s.tokens_per_step, "moe_expert_layers": s.steps * s.expert_layers,
            **{k: int(v) for k, v in jax.device_get(s.tally).items()}}


def _worst(pairs) -> float:
    return max(abs(float(got) - float(want)) / max(abs(float(want)), 1e-30) for got, want in pairs)


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


def _rate(hyper: dict, step: int) -> float:
    """The learning rate of the ``step``-th update, counting from 1."""
    warmup = hyper.get("warmup_steps", 0)
    return hyper["lr"] * (min(1.0, step / warmup) if warmup else 1.0)


def _router_with_experts(sums: dict) -> dict:
    sums = dict(sums)
    if "router" in sums:
        sums["experts"] = sums["experts"] + sums.pop("router")
    return sums


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
    # group), worst step.  Where the two sides' steps differ by a share r, in no
    # direction that the parameters know, this reads 10 r / (std(p) sqrt(n)) for a
    # group of n entries: the router's 262,144 alone would read r, so they go with
    # their experts
    hyper, decay = s.config["optimizer"], {}
    for i, (g, w) in enumerate(pairs if hyper["weight_decay"] else []):
        got_dot, want_dot, squares = (_router_with_experts(d) for d in (
            g["update_dot_params"], w["update_dot_params"], w["params_squared"]))
        for name, square in squares.items():
            err = abs(float(got_dot[name]) - float(want_dot[name])) / (
                _rate(hyper, i + 1) * hyper["weight_decay"] * float(square))
            decay[name] = max(decay.get(name, 0.0), err)
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
    """Parameters that a token multiplies, by kind, from the shapes: the
    convolution's and attention's projections, the dense FFN, one expert, the
    router, the output head (the tied embedding)."""
    d, heads, kv = config["hidden_size"], config["num_attention_heads"], config["num_key_value_heads"]
    head_dim = d // heads
    return {
        "conv": 3 * d * d + d * d,
        "attention": d * (heads + 2 * kv) * head_dim + heads * head_dim * d,
        "dense_ffn": 3 * d * config["intermediate_size"],
        "expert": 3 * d * config["moe_intermediate_size"],
        "router": d * config["num_experts_routed"],
        "head": config["vocab_size"] * d,
    }


def work(config: dict, traffic: dict, chips: int) -> dict:
    """Model operations of one step, recomputation not counted: 6 for every
    parameter a token multiplies (forward 2, backward 4), an expert layer's
    experts at the expected rows (``tokens x k x held / routed``), plus causal
    attention (forward ``2 S^2 d`` a head and sequence, backward twice that).
    ``kernels`` holds what each named kernel needs by its shapes alone."""
    p = matmul_parameters(config)
    seqs, length = traffic["sequences"], traffic["sequence_length"]
    tokens = seqs * length
    kinds = config["layer_types"]
    n_dense = config["num_dense_layers"]
    n_expert_layers = len(kinds) - n_dense
    d, heads = config["hidden_size"], config["num_attention_heads"]
    rows = tokens * config["num_experts_per_tok"] * config["num_experts"] // config["num_experts_routed"]
    per_token = (sum(p["conv"] if k == "conv" else p["attention"] for k in kinds)
                 + n_dense * p["dense_ffn"] + n_expert_layers * p["router"] + p["head"])
    experts_flop = 6 * rows * p["expert"] * n_expert_layers
    n_attention = sum(k == "full_attention" for k in kinds)
    attention_flop = 3 * 2 * length * length * (d // heads) * heads * seqs * n_attention
    n_params = (sum(p["conv"] + d * config["conv_L_cache"] if k == "conv" else p["attention"] for k in kinds)
                + n_dense * p["dense_ffn"] + p["head"]
                + n_expert_layers * (p["router"] + config["num_experts"] * p["expert"]))
    act = jnp.dtype(config.get("activation_dtype") or "float32").itemsize
    n_conv = sum(k == "conv" for k in kinds)
    return {
        "flop": 6 * tokens * per_token + experts_flop + attention_flop,
        # the least a step moves: parameters, gradient and both moments read and written
        "bytes": 28 * n_params,
        "derived": {"tokens_per_job": tokens, "steps_per_job": 1},
        "kernels": {
            # ``moe_experts_roofline`` puts the counted rows in the place of the expected
            "moe_experts": {"flop": experts_flop, "scope": "ht.moe.experts",
                            "bytes": n_expert_layers * (config["num_experts"] * p["expert"] * 4
                                                        + rows * 4 * d * act)},
            # forward reads [B, C, u] and writes the result (4 D a token), backward reads
            # them and the cotangent and writes three cotangents (7 D): memory-bound
            "shortconv": {"flop": n_conv * tokens * d * 30, "bytes": n_conv * tokens * d * 11 * act,
                          "scope": "ht.shortconv"},
            "flash_attention": {"flop": attention_flop, "scope": "ht.attention",
                                "bytes": n_attention * tokens * d * 8 * act},
        },
    }
