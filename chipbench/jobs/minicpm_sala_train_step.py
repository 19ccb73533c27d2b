"""Job ``minicpm_sala_train_step``: one training step of a MiniCPM-SALA
(``minicpm_sala``) causal language model (learned block-sparse attention
beside decayed linear attention, MiniCPM's muP scales, a dense SwiGLU) through
``ht.nn.DataParallel.make_train_step``: forward, next-token loss, backward and
the AdamW update in one jitted program, parameters and optimizer state
donated.  The loss is ``PatternLM.next_token_loss``'s (the final norm, the
head's scale and product and the log-sum-exp ``loss_block_rows`` rows at a
time), handed to the step as ``forward=``.  The step's batches are
``lm_train_step``'s; the model, the reference (``references/minicpm_sala.py``),
the limits, the counters and the count of work are this file's.

Configuration keys: the public ``config.json``'s own (``hidden_size``,
``mixer_types``, ``lightning_nh``, ``scale_emb``, ``scale_depth``,
``dim_model_base`` ..., read by ``model()`` and by the reference), the widths
as published; the shares held here (``heads_held``, ``kv_heads_held``,
``lightning_heads_held``, ``ffn_held``); ``sparse_config`` (the selection's
sizes), ``published`` (``num_hidden_layers``: the residual scale's and the
decays' depth), ``lightning_chunk``, ``loss_block_rows``,
``activation_dtype``, ``init_std`` and ``optimizer`` (AdamW's ``lr``, ``b1``,
``b2``, ``eps``, ``weight_decay`` and ``warmup_steps``).  Traffic keys:
``sequences``, ``sequence_length``, ``zipf_exponent``, ``check_steps``.  The
batch of step ``i`` is drawn on the device from ``(seed, i)`` inside the job:
token ids Zipf over the vocabulary, id 0 the most frequent, no padding.

The initial parameters are the reference's draw from ``(seed, configuration)``
(``reference.init_params``), handed to the trainer as a checkpoint would be.
A step returns, beside the sums by parameter group, each sparse layer's block
choices; ``check`` compares the first ``check_steps`` steps' choices with the
float32 reference's own by score (a block the program chose and the reference
did not must score within ``select_margin`` of the reference's lowest chosen
block, every block the reference must keep is kept, no row keeps fewer
distinct blocks than the reference's, and the pairs the program counted as
kept are those the shapes give), then replays those
steps from the same seeded parameters and batches with the reference on the
program's choices (as the expert jobs replay a routing) and its plain AdamW,
and compares loss and, by parameter group, gradient norms, the parameters'
steps, both moments and the decay with what the timed path returned.
"""

from __future__ import annotations

import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import optax

import heat_tpu as ht
from chipbench.jobs.lm_train_step import _batches, _rate, _worst
from chipbench.jobs.trinity_train_step import _loss
from chipbench.references import minicpm_sala as reference
from heat_tpu.nn.models import PatternLM
from heat_tpu.ops.sparse_attention import SparseConfig

# The timed path keeps float32 parameters and multiplies bfloat16 operands into
# float32 sums; the reference is float32 throughout.  Each limit lies between the
# largest reading of the sound runs (7 seeds, 3 replayed steps each) and the readings
# of two controls on one of them that round the operands of the reference's products
# one format below bfloat16 (float8_e4m3fn, float8_e5m2, in that order); the
# reference without its decay (no_decay) fails four (on a TPU v5e; PERF.md has every
# reading).
LIMITS = {
    # a block the program chose and the reference did not: (reference's lowest chosen
    # score - its score) / that lowest score, worst row, layer and step.  The scores
    # are sums of softmax weights over 2,047 compressed keys made from bfloat16 q and
    # K^c where the reference's are float32, so near a tie the orders differ (0.25% of
    # the choices): sound 1.19e-3 to 1.72e-3; float8 1.85e-2 and 2.51e-2
    "select_margin": 6e-3,
    # blocks the reference must keep (the first, the local window) that the program
    # left out, all rows, layers and steps: 0 in every run
    "forced_missing": 0,
    # rows whose distinct blocks are fewer than the reference's: with the margin above,
    # each block the reference chose and the program did not has a stand-in that scores
    # within the margin.  0 in every run
    "rows_short": 0,
    # |pairs of a query token and a key the program counted as kept - the shapes'
    # (``kept_pairs``)|, worst step: the counter ``sparse_kept_share`` reads.  0 in every run
    "kept_pairs_off": 0,
    # |loss - reference| / reference, worst of the replayed steps: sound 6.3e-7 to
    # 2.4e-6; float8 1.76e-4 and 1.71e-4; no_decay 8.4e-5
    "loss_err": 2e-5,
    # |norm - reference| / reference, worst parameter group (the sparse layer's
    # operator in most runs) and step: sound 2.9e-3 to 4.1e-3; float8 2,789 and 1,835
    "grad_norm_err": 2e-2,
    # | |p' - p| - reference's | / reference's, worst parameter group and step: sound
    # 6.0e-5 to 4.3e-4; float8 9.6e26 and 9.6e26; a state left unchanged reads 1
    "update_err": 1e-2,
    # the same of AdamW's new moments m and v: sound 4.8e-3 to 8.4e-3; float8 1.7e28
    "moment_err": 5e-2,
    # (p' - p) . p against the reference's in units of lr * weight_decay * |p|^2,
    # worst group and step: sound 8.9e-3 to 1.85e-2; float8 4.24 and 4.22
    "decay_err": 0.1,
    # 1 where the last timed step's loss is not finite
    "loss_not_finite": 0,
}

KINDS = {reference.SPARSE: "sparse_attention", reference.LIGHTNING: "lightning"}


def _range(config, key):
    lo, hi = config[key]
    return range(lo, hi)


def model(config: dict) -> PatternLM:
    dtype = config.get("activation_dtype")
    if not (config["use_output_gate"] and config["use_output_norm"] and config["attn_use_output_gate"]):
        raise ValueError("the lightning layers' output norm and gate and the sparse layers' gate are on here")
    if config["lightning_nkv"] != config["lightning_nh"] or len(config["mixer_types"]) != config["num_hidden_layers"]:
        raise ValueError("lightning_nkv is not lightning_nh, or mixer_types not a layer each")
    rope = ((("sparse_attention",) if config["attn_use_rope"] else ())
            + (("lightning",) if config["lightning_use_rope"] else ()))
    published = config["published"]["num_hidden_layers"]
    return PatternLM(
        config["vocab_size"], config["hidden_size"], [KINDS[k] for k in config["mixer_types"]],
        num_heads=config["num_attention_heads"], num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], qk_norm=config["qk_norm"], rope_kinds=rope,
        rope_base=config["rope_theta"], ffn_dim=config["intermediate_size"], norm_eps=config["rms_norm_eps"],
        init_std=config["init_std"], dtype=None if dtype is None else jnp.dtype(dtype),
        tie_embedding=config["tie_word_embeddings"], attention_gate=True,
        embedding_scale=config["scale_emb"], sparse=SparseConfig(**config["sparse_config"]),
        lightning_heads=config["lightning_nh"], lightning_head_dim=config["lightning_head_dim"],
        lightning_chunk=config.get("lightning_chunk", 128), published_layers=published,
        heads_held=_range(config, "heads_held"), kv_heads_held=_range(config, "kv_heads_held"),
        lightning_heads_held=_range(config, "lightning_heads_held"), ffn_held=_range(config, "ffn_held"),
        residual_scale=config["scale_depth"] / published ** 0.5,
        head_input_scale=config["dim_model_base"] / config["hidden_size"])


def _stats(grads, counts, params, new_params, new_state):
    """By parameter group (``reference.group_of``, which reads the program's
    tree as the reference's: the names are the same) the norms of the
    gradient, of the parameters' change and of both moments, the change's
    product with the parameters; the pairs the sparse layers kept and their
    block choices."""
    with jax.named_scope("ht.optim.update"):
        moved = jax.tree.map(jnp.subtract, new_params, params)
        update = {"update_norms": reference.group_norms(moved),
                  "m_norms": reference.group_norms(optax.tree_utils.tree_get(new_state, "mu")),
                  "v_norms": reference.group_norms(optax.tree_utils.tree_get(new_state, "nu")),
                  "update_dot_params": reference.group_sums(moved, params)}
    return {"grad_norms": reference.group_norms(grads), **update,
            "kept_pairs": sum(c["kept_pairs"] for c in counts),
            "causal_pairs": sum(c["causal_pairs"] for c in counts),
            "selection": [c["selection"] for c in counts if "selection" in c]}


def _draw(config: dict):
    """``key -> parameters``: the reference's draw, on the device in one program."""
    return jax.jit(functools.partial(reference.init_params, cfg=config, init_std=config["init_std"]))


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
        batch=_batches(config, traffic, seed), steps=0, log=[],
        tokens_per_step=traffic["sequences"] * traffic["sequence_length"],
        tally={k: jnp.zeros((), jnp.float32) for k in ("sparse_kept_pairs", "sparse_causal_pairs")},
    )


@jax.jit
def _tally(tally, kept, causal):
    """The run's tallies plus one step's pairs, float32 (a step's are exact
    int32; their sum over a window outgrows it)."""
    return {"sparse_kept_pairs": tally["sparse_kept_pairs"] + kept.astype(jnp.float32),
            "sparse_causal_pairs": tally["sparse_causal_pairs"] + causal.astype(jnp.float32)}


def job(s):
    tokens = s.batch(s.steps)
    with jax.profiler.TraceAnnotation("ht.nn.DataParallel.train_step"):
        s.params, s.opt_state, loss, stats = s.step(s.params, s.opt_state, tokens, tokens)
    s.steps += 1
    s.tally = _tally(s.tally, stats["kept_pairs"], stats["causal_pairs"])
    # the block choices of the steps the check replays, no later ones
    s.log.append((loss, stats if s.steps <= s.traffic["check_steps"] else {**stats, "selection": None}))
    return loss, stats


def counters(s) -> dict:
    """Tallies over all steps so far: tokens and, from the device, the pairs of
    a query token and a key the sparse layers kept and the pairs of their
    causal triangles."""
    return {"tokens": s.steps * s.tokens_per_step,
            **{k: float(v) for k, v in jax.device_get(s.tally).items()}}


def replay(s, steps: int, selections, **lower):
    """The first ``steps`` steps by the plain reference on the program's block
    choices ``selections`` (a list a step, each a list a sparse layer), from
    the seeded initial parameters and the seeded batches, each as a dict: loss,
    the selection's facts, and by parameter group the norms of the gradient,
    of the parameters' change and of both moments, the change's product with
    the parameters and the parameters' squares.  ``lower`` is passed to the
    reference (the controls)."""
    cfg, hyper = s.config, s.config["optimizer"]

    def one(params, adam, tokens, chosen):
        loss, facts, grads = reference.loss_and_grads(params, tokens, cfg, chosen, **lower)
        new, adam = reference.adamw_step(params, grads, adam, **hyper)
        moved = jax.tree.map(jnp.subtract, new, params)
        return new, adam, {
            "loss": loss, "select": _select_facts(facts, chosen),
            "grad_norms": reference.group_norms(grads), "update_norms": reference.group_norms(moved),
            "m_norms": reference.group_norms(adam["m"]), "v_norms": reference.group_norms(adam["v"]),
            "update_dot_params": reference.group_sums(moved, params),
            "params_squared": reference.group_sums(params, params)}

    one = jax.jit(one, donate_argnums=(0, 1))
    params = s.draw(jax.random.key(s.seed))
    adam = reference.adamw_init(params)
    out = []
    for i in range(steps):
        params, adam, facts = one(params, adam, s.batch(i), selections[i])
        out.append(jax.device_get(facts))
    return out


def _select_facts(facts, chosen):
    """Of the program's choices against the reference's own, over the sparse
    layers: the worst relative margin of a block it chose and the reference
    did not, the blocks the reference must keep that it left out, the rows
    with fewer distinct blocks than the reference's, the choices that agree
    and all of them."""
    margin = missing = short = same = total = jnp.zeros((), jnp.float32)
    for f, mine in zip(facts, chosen):
        own, kth, picked = f["own"], f["kth"][..., None], f["picked"]
        theirs = jnp.any(mine[..., :, None] == own[..., None, :], axis=-1) & (mine >= 0)
        # a block after the row, or one more where the reference keeps every block: no margin
        gap = jnp.where(jnp.isfinite(kth), (kth - picked) / jnp.maximum(jnp.abs(kth), 1e-30), jnp.inf)
        gap = jnp.where(theirs | (mine < 0), 0.0, jnp.where(picked == -jnp.inf, jnp.inf, gap))
        margin = jnp.maximum(margin, jnp.max(gap))
        kept = jnp.any(own[..., :, None] == mine[..., None, :], axis=-1)
        missing = missing + jnp.sum(f["forced"] & ~kept)
        topk = mine.shape[-1]
        again = jnp.any((mine[..., :, None] == mine[..., None, :]) & jnp.tri(topk, k=-1, dtype=bool), axis=-1)
        distinct = jnp.sum((mine >= 0) & ~again, axis=-1)
        short = short + jnp.sum(distinct < jnp.sum(own >= 0, axis=-1))
        same = same + jnp.sum(theirs)
        total = total + jnp.sum(own >= 0)
    return {"margin": margin, "missing": missing, "short": short, "same": same, "total": total}


def compare(s, out, **lower) -> tuple:
    steps = min(s.traffic["check_steps"], len(s.log))
    got = jax.device_get(s.log[:steps])
    selections = [g[1]["selection"] for g in s.log[:steps]]
    last_loss = float(out[0])
    # the replay needs the room the timed path's parameters and moments take
    for leaf in jax.tree.leaves((s.params, s.opt_state)):
        leaf.delete()
    s.params = s.opt_state = None
    want = replay(s, steps, selections, **lower)
    pairs = [(g[1], w) for g, w in zip(got, want)]

    def worst(*kinds):
        return _worst((g[kind][name], w[kind][name]) for g, w in pairs for kind in kinds for name in w[kind])

    hyper, decay = s.config["optimizer"], {}
    for i, (g, w) in enumerate(pairs if hyper["weight_decay"] else []):
        for name, square in w["params_squared"].items():
            if name not in ("embedding", "norms"):
                err = abs(float(g["update_dot_params"][name]) - float(w["update_dot_params"][name])) / (
                    _rate(hyper, i + 1) * hyper["weight_decay"] * float(square))
                decay[name] = max(decay.get(name, 0.0), err)
    by_group = lambda kind: {  # noqa: E731  (which group carries a limit's reading)
        name: _worst((g[kind][name], w[kind][name]) for g, w in pairs) for name in want[0][kind]}
    select = [w["select"] for w in want]
    cfg, traffic = s.config, s.traffic
    shapes_kept = (kept_pairs(traffic["sequence_length"], cfg["sparse_config"]) * traffic["sequences"]
                   * (cfg["kv_heads_held"][1] - cfg["kv_heads_held"][0]) * cfg["mixer_types"].count(reference.SPARSE))
    facts = {
        "select_margin": max(float(f["margin"]) for f in select),
        "forced_missing": int(sum(float(f["missing"]) for f in select)),
        "rows_short": int(sum(float(f["short"]) for f in select)),
        "kept_pairs_off": max(abs(int(g[1]["kept_pairs"]) - shapes_kept) for g in got),
        "loss_err": _worst((g[0], w["loss"]) for g, w in zip(got, want)),
        "grad_norm_err": worst("grad_norms"),
        "update_err": worst("update_norms"),
        "moment_err": worst("m_norms", "v_norms"),
        "decay_err": max(decay.values(), default=0.0),
        "loss_not_finite": int(not np.isfinite(last_loss)),
        "steps_compared": steps,
        "select_same_share": [float(f["same"]) / max(float(f["total"]), 1.0) for f in select],
        "select_margin_by_step": [float(f["margin"]) for f in select],
        "losses": [float(g[0]) for g in got],
        "reference_losses": [float(w["loss"]) for w in want],
        "kept_share_step0": float(pairs[0][0]["kept_pairs"]) / float(pairs[0][0]["causal_pairs"]),
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
    """The first steps' block choices against the float32 reference's, then
    their losses and, by parameter group, gradient norms, parameter steps and
    moments against the reference's replay on those choices."""
    return compare(s, out)


def kept_pairs(length: int, sc: dict) -> int:
    """Pairs of a query token and a key that a sparse layer keeps at a
    sequence of ``length``, from the shapes: every earlier key up to
    ``dense_len``; past it, where a token has more than ``topk`` blocks at
    or before it, ``topk - 1`` whole blocks and its own block up to itself."""
    if length <= sc["dense_len"]:
        return length * (length + 1) // 2
    size, topk = sc["block_size"], sc["topk"]
    i = np.arange(length, dtype=np.int64)
    whole = np.minimum(i // size, topk - 1) * size + i % size + 1
    return int(np.sum(np.where(i // size + 1 <= topk, i + 1, whole)))


def matmul_parameters(config: dict) -> dict:
    """Parameters that a token multiplies, by kind, from the shapes held: a
    sparse layer's projections with its gate, a lightning layer's, the
    SwiGLU, the output head."""
    d, w = config["hidden_size"], config["head_dim"]
    (lo, hi), (klo, khi) = config["heads_held"], config["kv_heads_held"]
    lp = (config["lightning_heads_held"][1] - config["lightning_heads_held"][0]) * config["lightning_head_dim"]
    flo, fhi = config["ffn_held"]
    return {"sparse": d * ((hi - lo) + 2 * (khi - klo)) * w + 2 * (hi - lo) * w * d,
            "lightning": 5 * d * lp, "swiglu": 3 * d * (fhi - flo), "head": config["vocab_size"] * d}


def parameters(config: dict) -> int:
    """All parameters held here: the matrices, the embedding, and the vectors
    (two norms a layer, the QK norms, a lightning layer's output norm, the
    final norm)."""
    p, d = matmul_parameters(config), config["hidden_size"]
    kinds = config["mixer_types"]
    head, lp = config["head_dim"], config["lightning_head_dim"]
    lightning_heads = config["lightning_heads_held"][1] - config["lightning_heads_held"][0]
    sparse = p["sparse"] + 2 * head
    light = p["lightning"] + lightning_heads * lp + (2 * lp if config["qk_norm"] else 0)
    return (sum(sparse if k == reference.SPARSE else light for k in kinds)
            + len(kinds) * (p["swiglu"] + 2 * d) + 2 * p["head"] + d)


def work(config: dict, traffic: dict, chips: int) -> dict:
    """Model operations of one step, recomputation not counted: 6 for every
    parameter a token multiplies (forward 2, backward 4); sparse attention
    ``6 (d_qk + d_v)`` for every pair of a query head and a key it keeps
    (``kept_pairs``: whatever computes them); lightning attention ``6 x 2 d_k
    d_v`` a token and head (the state's update and the output, forward and
    backward).  ``kernels`` holds what each named kernel needs by its shapes
    alone: the sparse kernel's operations and q, k, v, the output and their
    cotangents once each; lightning's the same bytes (it is memory-bound)."""
    p = matmul_parameters(config)
    seqs, length = traffic["sequences"], traffic["sequence_length"]
    tokens = seqs * length
    kinds = config["mixer_types"]
    n_sparse, n_light = kinds.count(reference.SPARSE), kinds.count(reference.LIGHTNING)
    heads = config["heads_held"][1] - config["heads_held"][0]
    kv = config["kv_heads_held"][1] - config["kv_heads_held"][0]
    lh = config["lightning_heads_held"][1] - config["lightning_heads_held"][0]
    w, lw = config["head_dim"], config["lightning_head_dim"]
    per_token = n_sparse * p["sparse"] + n_light * p["lightning"] + len(kinds) * p["swiglu"] + p["head"]
    sparse_flop = 6 * 2 * w * heads * seqs * kept_pairs(length, config["sparse_config"]) * n_sparse
    light_flop = 6 * 2 * lw * lw * lh * tokens * n_light
    act = jnp.dtype(config.get("activation_dtype") or "float32").itemsize
    return {
        "flop": 6 * tokens * per_token + sparse_flop + light_flop,
        # the least a step moves: parameters, gradient and both moments read and written
        "bytes": 28 * parameters(config),
        "derived": {"tokens_per_job": tokens, "steps_per_job": 1},
        "kernels": {
            # q and the output a query head, k and v a key/value head, each with its cotangent
            "sparse_attention": {"flop": sparse_flop, "scope": "ht.sparse_attention",
                                 "bytes": n_sparse * tokens * 2 * 2 * w * (heads + kv) * act},
            "lightning": {"flop": light_flop, "scope": "ht.lightning",
                          "bytes": n_light * tokens * 2 * 4 * lw * lh * act},
        },
    }
