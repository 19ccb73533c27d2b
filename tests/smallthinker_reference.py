"""A causal language model of windowed rotary attention beside global attention
without positions, with ReLU-gated experts routed on the layer's input
(``model_name`` ``smallthinker_21b_instruct``), written plainly.

Reference of the ``smallthinker_21b_a3b_ep4`` configuration (job
``smallthinker_train_step``) and of the CPU tests of the windowed flash
kernels' model path, ``nn.MultiheadAttention(head_dim=, window=)``,
``nn.MoE(activation="relu")`` with ``router_input`` and ``nn.models.PatternLM``
in this layout.  It follows the public ``config.json`` of
PowerInfer/SmallThinker-21BA3B-Instruct and the SmallThinker report
(arXiv:2507.20984); what neither says is listed under ``assumed`` in the
configuration.  Everything is float32 with ``highest`` matmul precision; no
``heat_tpu`` import, no kernel, no cache.  Attention is explicit masked
scores, the experts one after the other over the experts held, each over all
the tokens with a dense mask.

``x`` is a (sequences, positions, hidden) input, ``RMSNorm(x) = x /
sqrt(mean(x^2) + eps) * w``, no projection has a bias, a weight is stored
``(out, in)`` (an expert's ``(in, out)``, stacked over the experts held; the
router's ``(in, experts)``):

    layer l       logits = x W_r (the layer's input as it enters, before its norm);
                  sel = the k largest logits;  w = softmax(logits[sel]) over the k
                  z = RMSNorm(x);  q, k, v = split(W_qkv z): H query heads and
                  H_kv key/value heads, all ``head_dim`` wide (H head_dim is not
                  the hidden size); no normalisation of q or k
                  if rope_layout[l]: q, k rotated (rotate-half, rope_theta);
                  else no positions at all
                  scores[i, j] = q_i . k_j / sqrt(head_dim), kept where j <= i and,
                  if sliding_window_layout[l], i - j < sliding_window_size;
                  a group of query heads to each key/value head
                  h = x + W_o concat(softmax(scores) v)
                  u = RMSNorm(h)
                  y = h + sum_{e in sel, e held} w_e W_down,e (relu(W_gate,e u) * W_up,e u)
    ends          embedding, final RMSNorm, logits = h W_head^T (a matrix of its own),
                  loss = mean next-token cross-entropy

Departures from the published description, each for a reason: the router
reads the layer's input ahead of the input norm (``described_as`` says "router
placed before attention" and no key says which side of the norm; the public
llama.cpp graph takes its logits from the layer input ahead of ``attn_norm``);
``moe_primary_router_apply_softmax`` and ``norm_topk_prob`` together are a
softmax over the chosen logits, written as that; there are no secondary
experts (no key of ``config`` sizes one).

``experts_held`` (a range of expert ids) and the vocabulary are the
configuration's: the router always has ``num_experts_routed`` outputs and picks
``moe_num_active_primary_experts``; what the experts not held would add is
left out.

The functions take the parameters as the pytree ``PatternLM.init`` returns
(the same names and shapes), so gradients compare leaf by leaf, and
``init_params`` draws such a pytree from a key and the configuration's shapes
alone.  A head's scores are computed ``ROWS`` query rows at a time, one head
and one expert at a time, each rematerialised (one head's 16,384^2 float32
scores are 1 GiB): that only bounds the memory, the numbers are the same.
``product_dtype`` rounds the operands of every matrix product to a lower
precision first; ``no_window`` leaves the window out of the windowed layers and
``rope_everywhere`` rotates the global layers too: the controls that a
comparison must tell from the reference itself.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

ROWS = 2048  # query rows of one head scored at a time


def _mm(a, b, dtype=None):
    """``a @ b`` in float32 at ``highest`` precision; with ``dtype`` the
    operands are rounded to it first."""
    if dtype is not None:
        a, b = a.astype(dtype), b.astype(dtype)
    return jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=lax.Precision.HIGHEST)


def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rotate_half(x, positions, base):
    d = x.shape[-1]
    inv = base ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def _attend_rows(q, rows, k, v, window, dtype):
    """Query rows ``rows`` (their positions) of one head against all its
    keys: ``q`` (R, d), ``k`` and ``v`` (S, d)."""
    s = _mm(q, k.T, dtype) / jnp.sqrt(jnp.float32(q.shape[-1]))
    keys = jnp.arange(k.shape[0])[None, :]
    kept = keys <= rows[:, None]
    if window is not None:
        kept = kept & (rows[:, None] - keys < window)
    return _mm(jax.nn.softmax(jnp.where(kept, s, -jnp.inf), axis=-1), v, dtype)


def _attend(q, k, v, window, dtype):
    """One head over one sequence, ``ROWS`` query rows at a time."""
    length, d = q.shape
    step = min(ROWS, length)
    if length % step:
        step = length
    one = jax.checkpoint(functools.partial(_attend_rows, window=window, dtype=dtype))
    rows = jnp.arange(length).reshape(-1, step)
    return lax.map(lambda t: one(*t, k, v), (q.reshape(-1, step, d), rows)).reshape(length, d)


def attention(p, z, cfg, rotary: bool, window, dtype=None):
    heads, kv_heads, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    n, length, _ = z.shape
    qkv = _mm(z, p["in_proj_weight"].T, dtype)
    q, k, v = jnp.split(qkv, [heads * d, (heads + kv_heads) * d], axis=-1)
    by_head = lambda t, h: jnp.moveaxis(t.reshape(n, length, h, d), 1, 2)  # noqa: E731  (n, h, S, d)
    q, k, v = by_head(q, heads), by_head(k, kv_heads), by_head(v, kv_heads)
    if rotary:
        pos = jnp.arange(length)
        q, k = rotate_half(q, pos, cfg["rope_theta"]), rotate_half(k, pos, cfg["rope_theta"])
    group = heads // kv_heads  # query head h reads key/value head h // group
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    one = functools.partial(_attend, window=window, dtype=dtype)
    flat = lambda t: t.reshape((n * heads, length, d))  # noqa: E731
    out = lax.map(lambda t: one(*t), (flat(q), flat(k), flat(v)))
    out = jnp.moveaxis(out.reshape(n, heads, length, d), 1, 2).reshape(n, length, heads * d)
    return _mm(out, p["out_proj"]["weight"].T, dtype)


def route(p, r, cfg):
    """``(weights (tokens, experts) with zeros off the selection, selection
    (tokens, k))`` over all the experts, from the router's own input ``r``."""
    logits = _mm(r, p["router"])
    picked, sel = lax.top_k(logits, cfg["moe_num_active_primary_experts"])
    picked = jax.nn.softmax(picked, axis=-1)
    onehot = jax.nn.one_hot(sel, cfg["num_experts_routed"], dtype=jnp.float32)  # (tokens, k, E)
    return jnp.einsum("tk,tke->te", picked, onehot), sel


def _expert(u, w, w1, w3, w2, dtype):
    """One expert's part of the output: ``w`` is its weight a token, 0 where
    the token did not choose it."""
    return w[:, None] * _mm(jax.nn.relu(_mm(u, w1, dtype)) * _mm(u, w3, dtype), w2, dtype)


def experts(p, u, r, cfg, dtype=None):
    """``(the held experts' part of the layer's output, rows routed to each
    expert held)``: the experts compute on ``u``, the router scores ``r``.
    One expert after the other over all the tokens, as a scan so that the
    program holds one expert's code and not one copy an expert."""
    lo, hi = cfg["experts_held"]
    shape = u.shape
    u, r = u.reshape(-1, shape[-1]), r.reshape(-1, shape[-1])
    weights, sel = route(p, r, cfg)
    one = jax.checkpoint(functools.partial(_expert, dtype=dtype))
    out, _ = lax.scan(lambda total, held: (total + one(u, *held), None), jnp.zeros_like(u),
                      (weights[:, lo:hi].T, p["w1"], p["w3"], p["w2"]))
    rows = jnp.sum(sel[:, :, None] == jnp.arange(lo, hi)[None, None, :], axis=(0, 1))
    return out.reshape(shape), rows


def block(p, x, layer, cfg, product_dtype=None, no_window=False, rope_everywhere=False):
    """Layer ``layer``: ``(y, rows routed to the experts held)``."""
    rotary = bool(cfg["rope_layout"][layer]) or rope_everywhere
    windowed = bool(cfg["sliding_window_layout"][layer]) and not no_window
    z = rms_norm(x, p["operator_norm"]["weight"], cfg["rms_norm_eps"])
    h = x + attention(p["operator"], z, cfg, rotary,
                      cfg["sliding_window_size"] if windowed else None, product_dtype)
    u = rms_norm(h, p["ffn_norm"]["weight"], cfg["rms_norm_eps"])
    out, rows = experts(p["ffn"], u, x, cfg, product_dtype)
    return h + out, rows


def hidden_states(params, tokens, cfg, **lower):
    """``(final normalised states, [rows per expert held] per layer)``."""
    x = params["embed"]["weight"][tokens]
    rows = []
    for layer, p in enumerate(params["blocks"]):
        x, r = jax.checkpoint(functools.partial(block, layer=layer, cfg=cfg, **lower))(p, x)
        rows.append(r)
    return rms_norm(x, params["norm"]["weight"], cfg["rms_norm_eps"]), rows


def logits(params, tokens, cfg, **lower):
    h, _ = hidden_states(params, tokens, cfg, **lower)
    return _mm(h, params["head"]["weight"].T, lower.get("product_dtype"))


def _sequence_nll(h, targets, head, dtype):
    """Summed next-token negative log-likelihood of one sequence."""
    lg = _mm(h[:-1], head.T, dtype)
    return jnp.sum(jax.nn.logsumexp(lg, axis=-1)
                   - jnp.take_along_axis(lg, targets[1:, None], axis=-1)[:, 0])


def loss(params, tokens, cfg, **lower):
    """``(mean next-token cross-entropy, rows per layer)``."""
    h, rows = hidden_states(params, tokens, cfg, **lower)
    one = jax.checkpoint(functools.partial(
        _sequence_nll, head=params["head"]["weight"], dtype=lower.get("product_dtype")))
    total = jnp.sum(lax.map(lambda t: one(*t), (h, tokens)))
    n, length = tokens.shape
    return total / (n * (length - 1)), rows


def loss_and_grads(params, tokens, cfg, **lower):
    """``(loss, rows, gradients)``."""
    (value, rows), grads = jax.value_and_grad(loss, has_aux=True)(params, tokens, cfg, **lower)
    return value, rows, grads


def _matrix(*shape):
    return ("normal", shape)


def _shapes(cfg) -> dict:
    """The parameters' names and shapes from the configuration: ``("normal",
    shape)`` for a matrix, ``("embed", shape)`` for the token embedding,
    ``("one", shape)`` for a norm's weight."""
    d, heads, kv = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    head, f, routed = cfg["head_dim"], cfg["moe_ffn_hidden_size"], cfg["num_experts_routed"]
    lo, hi = cfg["experts_held"]
    norm = lambda n: {"weight": ("one", (n,))}  # noqa: E731
    layer = {
        "operator_norm": norm(d),
        "operator": {"in_proj_weight": _matrix((heads + 2 * kv) * head, d),
                     "out_proj": {"weight": _matrix(d, heads * head)}},
        "ffn_norm": norm(d),
        "ffn": {"router": _matrix(d, routed), "w1": _matrix(hi - lo, d, f),
                "w3": _matrix(hi - lo, d, f), "w2": _matrix(hi - lo, f, d)},
    }
    return {"embed": {"weight": ("embed", (cfg["vocab_size"], d))},
            "blocks": [layer for _ in cfg["rope_layout"]], "norm": norm(d),
            "head": {"weight": _matrix(cfg["vocab_size"], d)}}


def init_params(key, cfg, init_std=0.02, embed_std=None):
    """Float32 parameters from ``key``: every matrix ``N(0, init_std^2)``, the
    token embedding ``N(0, embed_std^2)`` (``None``: as the matrices), every
    norm's weight 1; one draw a leaf, keyed by the leaf's place in the
    flattened ``_shapes(cfg)``."""
    is_leaf = lambda x: isinstance(x, tuple)  # noqa: E731
    flat, treedef = jax.tree_util.tree_flatten(_shapes(cfg), is_leaf=is_leaf)

    def draw(i, kind, shape):
        if kind == "one":
            return jnp.ones(shape, jnp.float32)
        std = init_std if kind == "normal" or embed_std is None else embed_std
        return std * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)

    return jax.tree_util.tree_unflatten(treedef, [draw(i, *leaf) for i, leaf in enumerate(flat)])


def _names(path) -> list:
    return [str(getattr(k, "key", getattr(k, "idx", ""))) for k in path]


def decays(path) -> bool:
    """Weight decay on every matrix, the output head among them; none on a
    norm's weight or on the embedding."""
    names = _names(path)
    return "embed" not in names and not any(n.endswith("norm") for n in names)


def adamw_init(params):
    return {"m": jax.tree.map(jnp.zeros_like, params), "v": jax.tree.map(jnp.zeros_like, params),
            "t": jnp.zeros((), jnp.int32)}


def adamw_step(params, grads, state, *, lr, b1, b2, eps, weight_decay, warmup_steps=0):
    """Loshchilov and Hutter's AdamW with bias correction, decoupled decay
    ``lr * weight_decay * p`` on the leaves ``decays`` names.  With
    ``warmup_steps`` the ``t``-th step (counting from 1) uses ``lr * min(1, t /
    warmup_steps)``."""
    t = state["t"] + 1
    c1, c2 = 1.0 - b1 ** t.astype(jnp.float32), 1.0 - b2 ** t.astype(jnp.float32)
    if warmup_steps:
        lr = lr * jnp.minimum(1.0, t.astype(jnp.float32) / warmup_steps)

    def leaf(path, p, g, m, v):
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
    return "experts" if names[2] == "ffn" else f"operator_{names[1]}"


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
