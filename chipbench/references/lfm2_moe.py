"""A causal language model of gated short convolutions, grouped-query attention
and sigmoid-routed experts (``model_type`` ``lfm2_moe``), written plainly.

Reference of the ``lfm2_8b_a1b_ep4`` configuration (job ``lm_train_step``) and
of the CPU tests of ``heat_tpu.nn.models.PatternLM``.  It follows the public
``config.json`` of LiquidAI/LFM2-8B-A1B; what that file does not say is listed
under ``assumed`` in the configuration.  Everything is float32 with ``highest``
matmul precision (on a TPU a float32 product otherwise runs in bfloat16
passes); no ``heat_tpu`` import, no kernel, no cache.  Attention is explicit
scores, the convolution an explicit sum over its taps, the experts a loop over
the experts held with a dense mask over the tokens.

``z`` is a (sequences, positions, hidden) input, ``RMSNorm(x) = x /
sqrt(mean(x^2) + eps) * g``, no projection has a bias, a weight is stored
``(out, in)`` (an expert's ``(in, out)``, stacked over the experts held):

    block l       h = x + Op_l(RMSNorm(x));  y = h + FFN_l(RMSNorm(h))
    conv          [B, C, u] = split3(W_in z);  v = B * u;
                  c_t = sum_j k_j v_{t-(L-1)+j} (zero before the sequence's start);
                  out = W_out (C * c)
    attention     q, k, v = split(W_qkv z);  q, k <- RMSNorm over the head;
                  rotate-half rotary positions; causal softmax(q k^T / sqrt(d)) v,
                  a group of query heads to each key/value head;  out = W_o
    dense FFN     W_2 (silu(W_1 z) * W_3 z)
    experts       s = sigmoid(z W_r);  sel = top_k(s + b);
                  w = s[sel] / (sum s[sel] + 1e-6) * routed_scaling_factor;
                  out = sum_{e in sel, e held} w_e E_e(z),  E_e a gated FFN
    ends          tied embedding, final RMSNorm, logits = h E^T,
                  loss = mean next-token cross-entropy

``experts_held`` (a range of expert ids) and the vocabulary are the
configuration's: the router always has ``num_experts`` outputs and picks
``num_experts_per_tok``; what the experts not held would add is left out.

The functions take the parameters as the pytree ``PatternLM.init`` returns
(the same names and shapes), so gradients compare leaf by leaf, and
``init_params`` draws such a pytree from a key and the configuration's shapes
alone, so that a comparison need not start from the program's own draw.  ``blocks``
(sequences x key/value heads at a time, one expert at a time, one sequence of
logits at a time, each rematerialised) only bounds the memory; the numbers are
the same.  ``product_dtype`` rounds the operands of every matrix product to a
lower precision first (``expert_product_dtype``: of the experts' products
only): the controls that a comparison must tell from the reference itself.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax


def _mm(a, b, dtype=None):
    """``a @ b`` in float32 at ``highest`` precision; with ``dtype`` the
    operands are rounded to it first."""
    if dtype is not None:
        a, b = a.astype(dtype), b.astype(dtype)
    return jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=lax.Precision.HIGHEST)


def rms_norm(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def short_conv(p, z, cfg, dtype=None):
    """The gated short convolution: position ``t`` sees ``t-(L-1)..t`` of its
    own sequence."""
    taps = cfg["conv_L_cache"]
    b, c, u = jnp.split(_mm(z, p["in_proj"]["weight"].T, dtype), 3, axis=-1)
    v = b * u
    length = v.shape[1]
    padded = jnp.pad(v, ((0, 0), (taps - 1, 0), (0, 0)))
    k = p["conv"]["weight"]  # (hidden, taps)
    conv = sum(k[:, j] * padded[:, j:j + length] for j in range(taps))
    return _mm(c * conv, p["out_proj"]["weight"].T, dtype)


def rotate_half(x, positions, base):
    d = x.shape[-1]
    inv = base ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def _attend(q, k, v, dtype):
    """One key/value head and its group of query heads over one sequence:
    ``q`` (group, S, d), ``k`` and ``v`` (S, d)."""
    d = q.shape[-1]
    s = _mm(q, k.T, dtype) / jnp.sqrt(jnp.float32(d))
    length = s.shape[-1]
    causal = jnp.arange(length)[:, None] >= jnp.arange(length)[None, :]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    return _mm(p, v, dtype)


def attention(p, z, cfg, dtype=None):
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    n, length, hidden = z.shape
    d = hidden // heads
    group = heads // kv_heads
    qkv = _mm(z, p["in_proj_weight"].T, dtype)
    q, k, v = jnp.split(qkv, [hidden, hidden + kv_heads * d], axis=-1)
    q = q.reshape(n, length, kv_heads, group, d)
    k = k.reshape(n, length, kv_heads, d)
    v = v.reshape(n, length, kv_heads, d)
    q = rms_norm(q, p["q_norm"]["weight"], cfg["norm_eps"])
    k = rms_norm(k, p["k_norm"]["weight"], cfg["norm_eps"])
    pos = jnp.arange(length)
    q = jnp.moveaxis(q, 1, 3)  # (n, kv, group, S, d)
    k, v = jnp.moveaxis(k, 1, 2), jnp.moveaxis(v, 1, 2)  # (n, kv, S, d)
    q, k = rotate_half(q, pos, cfg["rope_theta"]), rotate_half(k, pos, cfg["rope_theta"])
    one = jax.checkpoint(functools.partial(_attend, dtype=dtype))
    flat = lax.map(lambda t: one(*t), (q.reshape((n * kv_heads,) + q.shape[2:]),
                                       k.reshape((n * kv_heads,) + k.shape[2:]),
                                       v.reshape((n * kv_heads,) + v.shape[2:])))
    out = flat.reshape(n, kv_heads, group, length, d)
    out = jnp.moveaxis(out, 3, 1).reshape(n, length, hidden)
    return _mm(out, p["out_proj"]["weight"].T, dtype)


def dense_ffn(p, z, dtype=None):
    gate = jax.nn.silu(_mm(z, p["w1"]["weight"].T, dtype))
    return _mm(gate * _mm(z, p["w3"]["weight"].T, dtype), p["w2"]["weight"].T, dtype)


def route(p, z, cfg):
    """``(weights (tokens, experts) with zeros off the selection, selection
    (tokens, k))`` over all the experts."""
    s = jax.nn.sigmoid(_mm(z, p["router"]))
    _, sel = lax.top_k(s + lax.stop_gradient(p["expert_bias"]), cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, sel, axis=-1)
    if cfg.get("norm_topk_prob", True):
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-6)
    picked = picked * cfg.get("routed_scaling_factor", 1.0)
    onehot = jax.nn.one_hot(sel, cfg["num_experts"], dtype=jnp.float32)  # (tokens, k, E)
    return jnp.einsum("tk,tke->te", picked, onehot), sel


def _expert(z, w1, w3, w2, dtype):
    return _mm(jax.nn.silu(_mm(z, w1, dtype)) * _mm(z, w3, dtype), w2, dtype)


def experts(p, z, cfg, dtype=None):
    """``(the held experts' part of the layer's output, rows routed to each
    expert held)``."""
    lo, hi = cfg["experts_held"]
    shape = z.shape
    z = z.reshape(-1, shape[-1])
    weights, sel = route(p, z, cfg)
    one = jax.checkpoint(functools.partial(_expert, dtype=dtype))
    out = jnp.zeros_like(z)
    for e in range(lo, hi):
        out = out + weights[:, e:e + 1] * one(z, p["w1"][e - lo], p["w3"][e - lo], p["w2"][e - lo])
    rows = jnp.sum(sel[:, :, None] == jnp.arange(lo, hi)[None, None, :], axis=(0, 1))
    return out.reshape(shape), rows


def block(p, x, kind, cfg, product_dtype=None, expert_product_dtype=None):
    """One layer: ``(y, rows routed to the experts held, or None)``."""
    z = rms_norm(x, p["operator_norm"]["weight"], cfg["norm_eps"])
    op = short_conv if kind == "conv" else attention
    h = x + op(p["operator"], z, cfg, product_dtype)
    z = rms_norm(h, p["ffn_norm"]["weight"], cfg["norm_eps"])
    if "router" in p["ffn"]:
        out, rows = experts(p["ffn"], z, cfg, expert_product_dtype or product_dtype)
        return h + out, rows
    return h + dense_ffn(p["ffn"], z, product_dtype), None


def hidden_states(params, tokens, cfg, **lower):
    """``(final normalised states, [rows per expert held] per expert layer)``."""
    x = params["embed"]["weight"][tokens]
    rows = []
    for p, kind in zip(params["blocks"], cfg["layer_types"]):
        x, r = jax.checkpoint(functools.partial(block, kind=kind, cfg=cfg, **lower))(p, x)
        if r is not None:
            rows.append(r)
    return rms_norm(x, params["norm"]["weight"], cfg["norm_eps"]), rows


def logits(params, tokens, cfg, **lower):
    h, _ = hidden_states(params, tokens, cfg, **lower)
    return _mm(h, params["embed"]["weight"].T, lower.get("product_dtype"))


def _sequence_nll(h, targets, embedding, dtype):
    """Summed next-token negative log-likelihood of one sequence."""
    lg = _mm(h[:-1], embedding.T, dtype)
    return jnp.sum(jax.nn.logsumexp(lg, axis=-1)
                   - jnp.take_along_axis(lg, targets[1:, None], axis=-1)[:, 0])


def loss(params, tokens, cfg, **lower):
    """``(mean next-token cross-entropy, rows per expert layer)``."""
    h, rows = hidden_states(params, tokens, cfg, **lower)
    one = jax.checkpoint(functools.partial(
        _sequence_nll, embedding=params["embed"]["weight"], dtype=lower.get("product_dtype")))
    total = jnp.sum(lax.map(lambda t: one(*t), (h, tokens)))
    n, length = tokens.shape
    return total / (n * (length - 1)), rows


def loss_and_grads(params, tokens, cfg, **lower):
    """``(loss, rows, gradients)``; the selection bias gets a zero gradient."""
    (value, rows), grads = jax.value_and_grad(loss, has_aux=True)(params, tokens, cfg, **lower)
    return value, rows, grads


def _matrix(shape):
    return ("normal", shape)


def _shapes(cfg) -> dict:
    """The parameters' names and shapes from the configuration: ``("normal",
    shape)`` for a matrix, ``("one", shape)`` for a norm's weight, ``("bias",
    shape)`` for the selection bias."""
    d, heads, kv = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    head, lo_hi = d // heads, cfg.get("experts_held")
    norm = lambda n: {"weight": ("one", (n,))}  # noqa: E731
    blocks = []
    for i, kind in enumerate(cfg["layer_types"]):
        if kind == "conv":
            operator = {"in_proj": {"weight": _matrix((3 * d, d))},
                        "conv": {"weight": _matrix((d, cfg["conv_L_cache"]))},
                        "out_proj": {"weight": _matrix((d, d))}}
        else:
            operator = {"in_proj_weight": _matrix(((heads + 2 * kv) * head, d)),
                        "q_norm": norm(head), "k_norm": norm(head),
                        "out_proj": {"weight": _matrix((d, d))}}
        if i < cfg["num_dense_layers"]:
            f = cfg["intermediate_size"]
            ffn = {"w1": {"weight": _matrix((f, d))}, "w3": {"weight": _matrix((f, d))},
                   "w2": {"weight": _matrix((d, f))}}
        else:
            f, routed = cfg["moe_intermediate_size"], cfg["num_experts"]
            held = routed if lo_hi is None else lo_hi[1] - lo_hi[0]
            ffn = {"router": _matrix((d, routed)), "expert_bias": ("bias", (routed,)),
                   "w1": _matrix((held, d, f)), "w3": _matrix((held, d, f)),
                   "w2": _matrix((held, f, d))}
        blocks.append({"operator_norm": norm(d), "operator": operator, "ffn_norm": norm(d), "ffn": ffn})
    return {"embed": {"weight": _matrix((cfg["vocab_size"], d))}, "blocks": blocks, "norm": norm(d)}


def init_params(key, cfg, init_std=0.02, bias_std=0.0):
    """Float32 parameters from ``key``: every matrix ``N(0, init_std^2)``,
    every norm's weight 1, the selection bias ``N(0, bias_std^2)``; one draw a
    leaf, keyed by the leaf's place in the flattened ``_shapes(cfg)``."""
    is_leaf = lambda x: isinstance(x, tuple)  # noqa: E731
    flat, treedef = jax.tree_util.tree_flatten(_shapes(cfg), is_leaf=is_leaf)

    def draw(i, kind, shape):
        if kind == "one":
            return jnp.ones(shape, jnp.float32)
        std = bias_std if kind == "bias" else init_std
        return std * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)

    return jax.tree_util.tree_unflatten(treedef, [draw(i, *leaf) for i, leaf in enumerate(flat)])


def decays(path) -> bool:
    """Weight decay everywhere but on a norm's weight, on the selection bias
    and on the embedding."""
    names = [str(getattr(k, "key", getattr(k, "idx", ""))) for k in path]
    if "embed" in names or "expert_bias" in names:
        return False
    return not any(n.endswith("norm") for n in names)


def trainable(path) -> bool:
    return "expert_bias" not in [str(getattr(k, "key", "")) for k in path]


def adamw_init(params):
    zeros = jax.tree.map(jnp.zeros_like, params)
    return {"m": zeros, "v": jax.tree.map(jnp.zeros_like, params), "t": jnp.zeros((), jnp.int32)}


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
        if not trainable(path):
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
    names = [str(getattr(k, "key", getattr(k, "idx", ""))) for k in path]
    if names[0] == "embed":
        return "embedding"
    if "expert_bias" in names:
        return "selection_bias"
    if any(n.endswith("norm") for n in names):
        return "norms"
    if "router" in names:
        return "router"
    if names[0] == "blocks" and names[2] == "ffn":
        return "experts" if names[3] in ("w1", "w2", "w3") and len(names) == 4 else "dense_ffn"
    if names[0] == "blocks" and names[2] == "operator":
        return f"operator_{names[1]}"
    return "other"


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
