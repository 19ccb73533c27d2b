"""A causal language model of multi-head latent attention with rotary
positions on its shared key part, in every layer, and sigmoid-routed experts
beside shared ones (``model_type`` ``deepseek_v3``), written plainly.

Reference of the ``moonlight_16b_a3b_ep8`` configuration (job
``moonlight_train_step``) and of the CPU tests of
``nn.LatentAttention(rope=True)`` and ``nn.models.PatternLM`` with ``"mla"``
in ``rope_kinds``.  It follows the public ``config.json`` of
moonshotai/Moonlight-16B-A3B and the layer's public code (DeepSeek-V3's
``modeling_deepseek.py``, which the model's repository ships); what no key of
the configuration states is listed under ``assumed`` in the configuration's
file.  Everything is float32 with ``highest`` matmul precision; no
``heat_tpu`` import, no kernel, no cache.  Attention is explicit masked
scores, the experts one after the other over the experts held, each over all
the tokens with a dense mask.

``x`` is a (sequences, positions, hidden) input, ``RMSNorm(x) = x /
sqrt(mean(x^2) + eps) * w``, no projection has a bias, a weight is stored
``(out, in)`` (an expert's ``(in, out)``, stacked over the experts held; the
router's ``(in, experts)``):

    block l       z = RMSNorm(x);  q = W_q z: H heads of qk_nope + qk_rope
                  (q_lora_rank null: no low-rank query);
                  [c, k_pe] = W_kva z (kv_lora_rank and qk_rope wide);
                  [k_nope, v] = W_kvb RMSNorm_kv(c): H heads of qk_nope and v_head_dim
                  q_pe = the last qk_rope channels of each query head; q_pe and k_pe
                  rotated at rope_theta, k_pe once and then the same for every head:
                  the channels de-interleaved ((2i, 2i+1) -> (i, d/2 + i)), then
                  x cos + rotate_half(x) sin, positions 0 .. S-1
                  k = [k_nope, k_pe];  scores = q . k / sqrt(qk_nope + qk_rope), kept
                  where the key is not later than the query (rope_scaling null: no mscale)
                  h = x + W_o concat(softmax(scores) v)
                  u = RMSNorm(h)
    l < first_k_dense_replace   y = h + W_2 (silu(W_1 u) * W_3 u)
    otherwise     s = sigmoid(u W_r);  sel = the k largest of s + b (b: the selection
                  bias, a buffer);  w = routed_scaling_factor s[sel] / (sum s[sel] + 1e-6)
                  y = h + E_shared(u) + sum_{e in sel, e held} w_e E_e(u),  E a gated
                  FFN, E_shared one of width n_shared_experts x moe_intermediate_size
    ends          final RMSNorm, logits = h W_head^T (a matrix of its own),
                  loss = mean next-token cross-entropy

Departures from the published code, each for a reason: the renormalisation
adds 1e-6 to the sum of the chosen scores where the published code adds 1e-20
(``nn.MoE._route``'s constant, shared with the other sigmoid-routed
configurations; the sum of 6 sigmoid scores is of order 3, so the weights
differ by 3e-7 of themselves, under float32's rounding of the sum); the
selection is a plain top-k over all experts (``n_group`` 1 and ``topk_group``
1 make ``noaux_tc``'s grouped one the same); the shared experts are added whole
on every rank, so an expert-parallel sum counts them once (``shared=``); the
step's loss has no auxiliary balance term and the selection bias does not move
(``assumed``).  The rotation keeps the published code's de-interleaved layout
of the rotated channels; scores and gradients are those of a rotation of
consecutive channel pairs, since the same permutation of the channels is
applied to the query and to the key.

``experts_held`` (a range of expert ids; absent: all) and the vocabulary are
the configuration's: the router always has ``num_experts_routed`` outputs
(absent: ``n_routed_experts``) and picks ``num_experts_per_tok``; what the
experts not held would add is left out.

The functions take the parameters as the pytree ``PatternLM.init`` returns
(the same names and shapes), so gradients compare leaf by leaf, and
``init_params`` draws such a pytree from a key and the configuration's shapes
alone.  A head's scores are computed ``ROWS`` query rows at a time, one head
and one expert at a time, the logits ``HEAD_ROWS`` rows at a time, a layer's
two sublayers one after the other, each rematerialised: that only bounds the
memory, the numbers are the same.  ``product_dtype`` rounds the operands of
every matrix product to a lower precision first, and ``no_rope`` leaves the
rotation out: the controls that a comparison must tell from the reference
itself.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

ROWS = 2048  # query rows of one head scored at a time
HEAD_ROWS = 4096  # rows of a sequence whose logits exist at a time
RENORM_EPS = 1e-6  # added to the sum of a token's chosen scores (published: 1e-20)


def _mm(a, b, dtype=None):
    """``a @ b`` in float32 at ``highest`` precision; with ``dtype`` the
    operands are rounded to it first."""
    if dtype is not None:
        a, b = a.astype(dtype), b.astype(dtype)
    return jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=lax.Precision.HIGHEST)


def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rotate(x, positions, base):
    """The published rotation of ``x`` (..., S, d): the channels
    de-interleaved, then rotated by halves (``rotate_half``)."""
    d = x.shape[-1]
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    inv = base ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def _attend_rows(q, rows, k, v, dtype):
    """Query rows ``rows`` (their positions) of one head: ``q`` (R, d_qk),
    ``k`` (S, d_qk), ``v`` (S, d_v)."""
    s = _mm(q, k.T, dtype) / jnp.sqrt(jnp.float32(q.shape[-1]))
    kept = jnp.arange(k.shape[0])[None, :] <= rows[:, None]
    return _mm(jax.nn.softmax(jnp.where(kept, s, -jnp.inf), axis=-1), v, dtype)


def _attend(q, k, v, dtype):
    """One head over one sequence, ``ROWS`` query rows at a time."""
    length = q.shape[0]
    step = min(ROWS, length)
    if length % step:
        step = length
    one = jax.checkpoint(functools.partial(_attend_rows, dtype=dtype))
    rows = jnp.arange(length).reshape(-1, step)
    out = lax.map(lambda t: one(*t, k, v), (q.reshape(-1, step, q.shape[-1]), rows))
    return out.reshape(length, v.shape[-1])


def attention(p, z, cfg, dtype=None, rotary: bool = True):
    """Latent attention of the normalised input ``z`` (n, S, hidden)."""
    heads, nope, rope = cfg["num_attention_heads"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, rank = cfg["v_head_dim"], cfg["kv_lora_rank"]
    n, length, _ = z.shape
    q = jnp.moveaxis(_mm(z, p["q_proj"]["weight"].T, dtype).reshape(n, length, heads, nope + rope), 2, 1)
    c, k_pe = jnp.split(_mm(z, p["kv_a_proj"]["weight"].T, dtype), [rank], axis=-1)
    c = rms_norm(c, p["kv_a_norm"]["weight"], cfg["kv_a_layernorm_eps"])
    kv = jnp.moveaxis(_mm(c, p["kv_b_proj"]["weight"].T, dtype).reshape(n, length, heads, nope + dv), 2, 1)
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    if rotary:
        pos = jnp.arange(length)
        q_pe, k_pe = rotate(q_pe, pos, cfg["rope_theta"]), rotate(k_pe, pos, cfg["rope_theta"])
    q = jnp.concatenate([q_nope, q_pe], axis=-1)  # (n, H, S, nope + rope)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe[:, None], (n, heads, length, rope))], axis=-1)
    flat = lambda t: t.reshape((n * heads, length, t.shape[-1]))  # noqa: E731
    one = functools.partial(_attend, dtype=dtype)
    out = lax.map(lambda t: one(*t), (flat(q), flat(k), flat(kv[..., nope:])))  # a head at a time
    out = jnp.moveaxis(out.reshape(n, heads, length, dv), 1, 2).reshape(n, length, heads * dv)
    return _mm(out, p["out_proj"]["weight"].T, dtype)


def _routed(cfg) -> int:
    return cfg.get("num_experts_routed", cfg["n_routed_experts"])


def _held(cfg) -> tuple:
    return tuple(cfg.get("experts_held") or (0, _routed(cfg)))


def route(p, u, cfg):
    """``(weights (tokens, experts) with zeros off the selection, selection
    (tokens, k))`` over all the experts."""
    s = jax.nn.sigmoid(_mm(u, p["router"]))
    _, sel = lax.top_k(s + lax.stop_gradient(p["expert_bias"]), cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, sel, axis=-1)
    if cfg.get("norm_topk_prob", True):
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + RENORM_EPS)
    picked = picked * cfg.get("routed_scaling_factor", 1.0)
    onehot = jax.nn.one_hot(sel, _routed(cfg), dtype=jnp.float32)  # (tokens, k, E)
    return jnp.einsum("tk,tke->te", picked, onehot), sel


def swiglu(p, u, dtype=None):
    gated = jax.nn.silu(_mm(u, p["w1"]["weight"].T, dtype)) * _mm(u, p["w3"]["weight"].T, dtype)
    return _mm(gated, p["w2"]["weight"].T, dtype)


def _expert(u, w, w1, w3, w2, dtype):
    """One expert's part of the output: ``w`` is its weight a token, 0 where
    the token did not choose it."""
    return w[:, None] * _mm(jax.nn.silu(_mm(u, w1, dtype)) * _mm(u, w3, dtype), w2, dtype)


def experts(p, u, cfg, dtype=None, shared: bool = True):
    """``(the held experts' part of the layer's output plus, with ``shared``,
    the shared experts', rows routed to each expert held)``.  One expert after
    the other over all the tokens, as a scan so that the program holds one
    expert's code and not one copy an expert."""
    lo, hi = _held(cfg)
    shape = u.shape
    u = u.reshape(-1, shape[-1])
    weights, sel = route(p, u, cfg)
    one = jax.checkpoint(functools.partial(_expert, dtype=dtype))
    out, _ = lax.scan(lambda total, held: (total + one(u, *held), None), jnp.zeros_like(u),
                      (weights[:, lo:hi].T, p["w1"], p["w3"], p["w2"]))
    if shared and cfg.get("n_shared_experts"):
        out = out + swiglu(p["shared"], u, dtype)
    rows = jnp.sum(sel[:, :, None] == jnp.arange(lo, hi)[None, None, :], axis=(0, 1))
    return out.reshape(shape), rows


def _attention_sublayer(p, x, cfg, product_dtype, no_rope):
    z = rms_norm(x, p["operator_norm"]["weight"], cfg["rms_norm_eps"])
    return x + attention(p["operator"], z, cfg, product_dtype, rotary=not no_rope)


def _ffn_sublayer(p, h, layer, cfg, product_dtype):
    u = rms_norm(h, p["ffn_norm"]["weight"], cfg["rms_norm_eps"])
    if layer < cfg["first_k_dense_replace"]:
        return h + swiglu(p["ffn"], u, product_dtype), None
    f, rows = experts(p["ffn"], u, cfg, product_dtype)
    return h + f, rows


def block(p, x, layer, cfg, product_dtype=None, no_rope=False):
    """Layer ``layer``: ``(y, rows routed to the experts held, or None)``.  The
    two sublayers are rematerialised one after the other, so that the backward
    pass holds one's intermediates at a time."""
    h = jax.checkpoint(functools.partial(
        _attention_sublayer, cfg=cfg, product_dtype=product_dtype, no_rope=no_rope))(p, x)
    return jax.checkpoint(functools.partial(
        _ffn_sublayer, layer=layer, cfg=cfg, product_dtype=product_dtype))(p, h)


def hidden_states(params, tokens, cfg, **lower):
    """``(final normalised states, [rows per expert held] per expert layer)``."""
    x = params["embed"]["weight"][tokens]
    rows = []
    for layer, p in enumerate(params["blocks"]):
        x, r = jax.checkpoint(functools.partial(block, layer=layer, cfg=cfg, **lower))(p, x)
        if r is not None:
            rows.append(r)
    return rms_norm(x, params["norm"]["weight"], cfg["rms_norm_eps"]), rows


def logits(params, tokens, cfg, **lower):
    h, _ = hidden_states(params, tokens, cfg, **lower)
    return _mm(h, params["head"]["weight"].T, lower.get("product_dtype"))


def _rows_nll(h, targets, counts, head, dtype):
    """Summed negative log-likelihood of the rows that count."""
    lg = _mm(h, head.T, dtype)
    picked = jnp.take_along_axis(lg, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(counts, jax.nn.logsumexp(lg, axis=-1) - picked, 0.0))


def loss(params, tokens, cfg, **lower):
    """``(mean next-token cross-entropy, rows per expert layer)``."""
    h, rows = hidden_states(params, tokens, cfg, **lower)
    n, length = tokens.shape
    step = HEAD_ROWS if length % HEAD_ROWS == 0 else length
    # position t predicts token t + 1; a sequence's last position predicts nothing
    targets = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    counts = jnp.broadcast_to(jnp.arange(length) < length - 1, (n, length))
    one = jax.checkpoint(functools.partial(
        _rows_nll, head=params["head"]["weight"], dtype=lower.get("product_dtype")))
    blocks = lambda a: a.reshape((-1, step) + a.shape[2:])  # noqa: E731
    total = jnp.sum(lax.map(lambda t: one(*t), (blocks(h), blocks(targets), blocks(counts))))
    return total / (n * (length - 1)), rows


def loss_and_grads(params, tokens, cfg, **lower):
    """``(loss, rows, gradients)``."""
    (value, rows), grads = jax.value_and_grad(loss, has_aux=True)(params, tokens, cfg, **lower)
    return value, rows, grads


def _matrix(*shape):
    return {"weight": ("normal", shape)}


def _shapes(cfg) -> dict:
    """The parameters' names and shapes from the configuration: ``("normal",
    shape)`` for a matrix and the token embedding, ``("one", shape)`` for a
    norm's weight, ``("bias", shape)`` for the selection bias."""
    d, heads, rank = cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    f, routed = cfg["moe_intermediate_size"], _routed(cfg)
    lo, hi = _held(cfg)
    norm = lambda n: {"weight": ("one", (n,))}  # noqa: E731
    swiglu_of = lambda w: {"w1": _matrix(w, d), "w3": _matrix(w, d), "w2": _matrix(d, w)}  # noqa: E731
    blocks = []
    for i in range(cfg["num_hidden_layers"]):
        if i < cfg["first_k_dense_replace"]:
            ffn = swiglu_of(cfg["intermediate_size"])
        else:
            ffn = {"router": ("normal", (d, routed)), "expert_bias": ("bias", (routed,)),
                   "w1": ("normal", (hi - lo, d, f)), "w3": ("normal", (hi - lo, d, f)),
                   "w2": ("normal", (hi - lo, f, d))}
            if cfg.get("n_shared_experts"):
                ffn["shared"] = swiglu_of(f * cfg["n_shared_experts"])
        operator = {"q_proj": _matrix(heads * (nope + rope), d), "kv_a_proj": _matrix(rank + rope, d),
                    "kv_a_norm": norm(rank), "kv_b_proj": _matrix(heads * (nope + dv), rank),
                    "out_proj": _matrix(d, heads * dv)}
        blocks.append({"operator_norm": norm(d), "operator": operator, "ffn_norm": norm(d), "ffn": ffn})
    return {"embed": _matrix(cfg["vocab_size"], d), "blocks": blocks, "norm": norm(d),
            "head": _matrix(cfg["vocab_size"], d)}


def init_params(key, cfg, init_std=0.02, bias_std=0.0):
    """Float32 parameters from ``key``: every matrix and the token embedding
    ``N(0, init_std^2)``, every norm's weight 1, the selection bias ``N(0,
    bias_std^2)``; one draw a leaf, keyed by the leaf's place in the flattened
    ``_shapes(cfg)``."""
    is_leaf = lambda x: isinstance(x, tuple)  # noqa: E731
    flat, treedef = jax.tree_util.tree_flatten(_shapes(cfg), is_leaf=is_leaf)

    def draw(i, kind, shape):
        if kind == "one":
            return jnp.ones(shape, jnp.float32)
        std = bias_std if kind == "bias" else init_std
        return std * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)

    return jax.tree_util.tree_unflatten(treedef, [draw(i, *leaf) for i, leaf in enumerate(flat)])


def even_out_bias(params, tokens, cfg, rounds: int = 48):
    """``params`` with each expert layer's selection bias set so that the
    experts' loads on ``tokens`` are even, a layer at a time from the first
    (a layer's input is computed with the biases below it set): minus each
    expert's mean score, then ``rounds`` of the published rule that moves the
    bias between steps (``b_e += g * sign(mean load - load_e)``) with a
    shrinking ``g``.  A trained ``noaux_tc`` router's bias holds the loads
    even; a drawn one leaves them to the weights' draw."""
    k, routed = cfg["num_experts_per_tok"], _routed(cfg)
    x = params["embed"]["weight"][tokens]
    blocks = []
    for layer, p in enumerate(params["blocks"]):
        h = _attention_sublayer(p, x, cfg, None, False)
        if layer >= cfg["first_k_dense_replace"]:
            u = rms_norm(h, p["ffn_norm"]["weight"], cfg["rms_norm_eps"]).reshape(-1, h.shape[-1])
            s = jax.nn.sigmoid(_mm(u, p["ffn"]["router"]))
            mean = jnp.mean(s, axis=0)

            def nudge(i, b):
                _, sel = lax.top_k(s + b, k)
                load = jnp.sum(jax.nn.one_hot(sel, routed, dtype=jnp.float32), axis=(0, 1))
                return b + 0.02 * 0.9 ** i * jnp.sign(jnp.mean(load) - load)

            bias = lax.fori_loop(0, rounds, nudge, jnp.mean(mean) - mean)
            p = {**p, "ffn": {**p["ffn"], "expert_bias": bias}}
        x, _ = _ffn_sublayer(p, h, layer, cfg, None)
        blocks.append(p)
    return {**params, "blocks": blocks}


def _names(path) -> list:
    return [str(getattr(k, "key", getattr(k, "idx", ""))) for k in path]


def decays(path) -> bool:
    """Weight decay on every matrix, the output head among them; none on a
    norm's weight, on the selection bias or on the embedding."""
    names = _names(path)
    return not ("embed" in names or "expert_bias" in names or any(n.endswith("norm") for n in names))


def adamw_init(params):
    return {"m": jax.tree.map(jnp.zeros_like, params), "v": jax.tree.map(jnp.zeros_like, params),
            "t": jnp.zeros((), jnp.int32)}


def adamw_step(params, grads, state, *, lr, b1, b2, eps, weight_decay, warmup_steps=0):
    """Loshchilov and Hutter's AdamW with bias correction, decoupled decay
    ``lr * weight_decay * p`` on the leaves ``decays`` names; the selection
    bias is a buffer and stays as it is.  With ``warmup_steps`` the ``t``-th
    step (counting from 1) uses ``lr * min(1, t / warmup_steps)``."""
    t = state["t"] + 1
    c1, c2 = 1.0 - b1 ** t.astype(jnp.float32), 1.0 - b2 ** t.astype(jnp.float32)
    if warmup_steps:
        lr = lr * jnp.minimum(1.0, t.astype(jnp.float32) / warmup_steps)

    def leaf(path, p, g, m, v):
        if "expert_bias" in _names(path):
            return p, m, v
        m = b1 * m + (1.0 - b1) * g
        v = b2 * v + (1.0 - b2) * g * g
        step = (m / c1) / (jnp.sqrt(v / c2) + eps)
        if decays(path):
            step = step + weight_decay * p
        return p - lr * step, m, v

    out = jax.tree_util.tree_map_with_path(leaf, params, grads, state["m"], state["v"])
    pick = lambda i: jax.tree.map(lambda _, o: o[i], params, out)  # noqa: E731
    return pick(0), {"m": pick(1), "v": pick(2), "t": t}


def group_of(path) -> str:
    """The parameter group a leaf's gradient norm is reported under."""
    names = _names(path)
    if names[0] in ("embed", "head"):
        return {"embed": "embedding", "head": "head"}[names[0]]
    if any(n.endswith("norm") for n in names):
        return "norms"
    if "router" in names:
        return "router"
    if "expert_bias" in names:
        return "selection_bias"
    if names[2] == "operator":
        return f"operator_{names[1]}"
    if "shared" in names:
        return "shared_expert"
    return "dense_ffn" if names[-1] == "weight" else "experts"  # an expert's matrices are stacked, bare


def group_sums(*trees) -> dict:
    """Over each parameter group, the sum of the product of the trees' leaves,
    entry by entry (a tree given twice: its squares)."""
    sums = {}
    flat = [jax.tree_util.tree_flatten_with_path(t)[0] for t in trees]
    for leaves in zip(*flat):
        name = group_of(leaves[0][0])
        product = functools.reduce(jnp.multiply, [a.astype(jnp.float32) for _, a in leaves])
        sums[name] = sums.get(name, 0.0) + jnp.sum(product)
    return sums


def group_norms(tree) -> dict:
    """The Euclidean norm of each parameter group's part of ``tree`` (the
    gradients, a step's change of the parameters, a moment of AdamW)."""
    return {name: jnp.sqrt(s) for name, s in group_sums(tree, tree).items()}
