"""A causal language model of gated grouped-query attention (windowed rotary
layers beside global layers without positions, queries and keys normalised a
head), a norm before and after every sublayer, an embedding scaled by the root
of the hidden size and sigmoid-routed experts beside a shared one
(``model_type`` ``afmoe``), written plainly.

Reference of the ``trinity_mini_26b_a3b_ep16`` configuration (job
``trinity_train_step``) and of the CPU tests of ``nn.MultiheadAttention(gate=True)``,
``nn.models.PatternLM(attention_gate=, output_norms=, embedding_scale=)`` and
``nn.losses.next_token_cross_entropy_by_rows``.  It follows the public
``config.json`` of arcee-ai/Trinity-Mini and the layer's public code
(``modeling_afmoe.py`` in Hugging Face Transformers); what no key of the
configuration states is listed under ``assumed`` in the configuration's file.
Everything is float32 with ``highest`` matmul precision; no ``heat_tpu``
import, no kernel, no cache.  Attention is explicit masked scores, the experts
one after the other over the experts held, each over all the tokens with a
dense mask.

``x`` is a (sequences, positions, hidden) input, ``RMSNorm(x) = x /
sqrt(mean(x^2) + eps) * w``, no projection has a bias, a weight is stored
``(out, in)`` (an expert's ``(in, out)``, stacked over the experts held; the
router's ``(in, experts)``):

    start         h_0 = sqrt(hidden_size) E[tokens]   (``mup_enabled``)
    layer l       z = RMSNorm_in(x);  q, k, v = split(W_qkv z): H query heads and
                  H_kv key/value heads, all ``head_dim`` wide;  g = W_g z (H head_dim wide)
                  q, k = RMSNorm_q(q), RMSNorm_k(k) over each head, one weight vector each
                  if layer_types[l] == "sliding_attention": q, k rotated (rotate-half,
                  rope_theta); else no positions at all
                  scores[i, j] = q_i . k_j / sqrt(head_dim), kept where j <= i and, in a
                  sliding layer, i - j < sliding_window; a group of query heads to each
                  key/value head
                  h = x + RMSNorm_post_attn(W_o (concat(softmax(scores) v) * sigmoid(g)))
                  u = RMSNorm_pre_mlp(h)
    l < num_dense_layers    f = W_2 (silu(W_1 u) * W_3 u)
    otherwise     s = sigmoid(u W_r);  sel = the k largest of s + b (b: the selection
                  bias, a buffer);  w = route_scale s[sel] / (sum s[sel] + 1e-6)
                  f = E_shared(u) + sum_{e in sel, e held} w_e E_e(u),  E a gated FFN
                  y = h + RMSNorm_post_mlp(f)
    ends          final RMSNorm, logits = h W_head^T (a matrix of its own),
                  loss = mean next-token cross-entropy

Departures from the published code, each for a reason: the renormalisation
adds 1e-6 to the sum of the chosen scores where the published code adds 1e-20
(``nn.MoE._route``'s constant, shared with two other configurations; the sum
of 8 sigmoid scores is of order 4, so the weights differ by 2.5e-7 of
themselves, under float32's rounding of the sum); the selection is a plain
top-k over all experts (``n_group`` 1 and ``topk_group`` 1 make the grouped
one the same); the shared expert is added whole on every rank, so an
expert-parallel sum counts it once (``shared=``); the step's loss has no
auxiliary balance term and the selection bias does not move (``assumed``).

``experts_held`` (a range of expert ids; absent: all) and the vocabulary are
the configuration's: the router always has ``num_experts_routed`` outputs
(absent: ``num_experts``) and picks ``num_experts_per_tok``; what the experts
not held would add is left out.

The functions take the parameters as the pytree ``PatternLM.init`` returns
(the same names and shapes), so gradients compare leaf by leaf, and
``init_params`` draws such a pytree from a key and the configuration's shapes
alone.  A head's scores are computed ``ROWS`` query rows at a time (a windowed
layer's against the ``ROWS + sliding_window`` keys that end with the block's
last row, which hold every key the mask keeps), one head and one expert at a
time, a key/value head's group of query heads at a time from the projection
to their part of the output, the logits ``HEAD_ROWS`` rows at a time, a
layer's two sublayers one after the other, each rematerialised (one head's 32,768^2 float32 scores are
4 GiB, a sequence's logits 3.3 GB): that only bounds the memory, the numbers
are the same.  ``product_dtype`` rounds the
operands of every matrix product to a lower precision first; ``no_gate``
leaves the attention's gate out, ``no_window`` the window of the sliding
layers and ``no_embedding_scale`` the embedding's factor: the controls that a
comparison must tell from the reference itself.
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


def rotate_half(x, positions, base):
    d = x.shape[-1]
    inv = base ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def _attend_rows(q, rows, k, v, window, dtype):
    """Query rows ``rows`` (their positions) of one head: ``q`` (R, d), ``k``
    and ``v`` (S, d).  Under a window only the ``R + window`` keys that end
    with the last row are scored (all of them where the sequence has no more):
    they hold every key the mask keeps."""
    length = k.shape[0]
    span = length if window is None else min(length, rows.shape[0] + window)
    first = jnp.maximum(rows[-1] + 1 - span, 0)
    k, v = lax.dynamic_slice_in_dim(k, first, span), lax.dynamic_slice_in_dim(v, first, span)
    s = _mm(q, k.T, dtype) / jnp.sqrt(jnp.float32(q.shape[-1]))
    keys = first + jnp.arange(span)[None, :]
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


def _attend_group(z, w_q, w_k, w_v, w_g, w_o, q_norm, k_norm, cfg, rotary, window, dtype, gate):
    """One key/value head and the query heads that read it, from the
    normalised input ``z`` (n, S, hidden) to their part of the layer's
    output: ``w_q`` and ``w_g`` (group d, hidden), ``w_k`` and ``w_v`` (d,
    hidden), ``w_o`` (hidden, group d)."""
    d, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    n, length, _ = z.shape
    q = jnp.moveaxis(_mm(z, w_q.T, dtype).reshape(n, length, -1, d), 1, 2)  # (n, group, S, d)
    k, v = _mm(z, w_k.T, dtype), _mm(z, w_v.T, dtype)  # (n, S, d)
    q, k = rms_norm(q, q_norm, eps), rms_norm(k, k_norm, eps)
    if rotary:
        pos = jnp.arange(length)
        q, k = rotate_half(q, pos, cfg["rope_theta"]), rotate_half(k, pos, cfg["rope_theta"])
    one = functools.partial(_attend, window=window, dtype=dtype)
    out = jax.vmap(lambda qs, ks, vs: lax.map(lambda qh: one(qh, ks, vs), qs))(q, k, v)  # a head at a time
    out = jnp.moveaxis(out, 1, 2).reshape(n, length, -1)
    if gate:
        out = out * jax.nn.sigmoid(_mm(z, w_g.T, dtype))
    return _mm(out, w_o.T, dtype)


def attention(p, z, cfg, rotary: bool, window, dtype=None, gate: bool = True):
    """Gated grouped-query attention of the normalised input ``z``: the sum
    over the key/value heads of each one's group, a group at a time and
    rematerialised (at the cell's size the 4,096-wide queries, gate and merged
    heads of a whole layer are 512 MB each)."""
    heads, kv_heads, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    w = p["in_proj_weight"]
    by_group = lambda rows: rows.reshape(kv_heads, -1, rows.shape[-1])  # noqa: E731
    w_q, w_k, w_v = (by_group(w[a:b]) for a, b in ((0, heads * d), (heads * d, (heads + kv_heads) * d),
                                                   ((heads + kv_heads) * d, (heads + 2 * kv_heads) * d)))
    w_g = by_group(p["gate_proj"]["weight"])
    w_o = jnp.moveaxis(p["out_proj"]["weight"].reshape(-1, kv_heads, (heads // kv_heads) * d), 1, 0)
    one = jax.checkpoint(functools.partial(
        _attend_group, q_norm=p["q_norm"]["weight"], k_norm=p["k_norm"]["weight"], cfg=cfg, rotary=rotary,
        window=window, dtype=dtype, gate=gate))
    total, _ = lax.scan(lambda total, group: (total + one(z, *group), None),
                        jnp.zeros(z.shape[:-1] + (w_o.shape[1],), jnp.float32), (w_q, w_k, w_v, w_g, w_o))
    return total


def _routed(cfg) -> int:
    return cfg.get("num_experts_routed", cfg["num_experts"])


def _held(cfg) -> tuple:
    return tuple(cfg.get("experts_held") or (0, _routed(cfg)))


def route(p, u, cfg):
    """``(weights (tokens, experts) with zeros off the selection, selection
    (tokens, k))`` over all the experts."""
    s = jax.nn.sigmoid(_mm(u, p["router"]))
    _, sel = lax.top_k(s + lax.stop_gradient(p["expert_bias"]), cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, sel, axis=-1)
    if cfg.get("route_norm", True):
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + RENORM_EPS)
    picked = picked * cfg.get("route_scale", 1.0)
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
    the shared expert's, rows routed to each expert held)``.  One expert after
    the other over all the tokens, as a scan so that the program holds one
    expert's code and not one copy an expert."""
    lo, hi = _held(cfg)
    shape = u.shape
    u = u.reshape(-1, shape[-1])
    weights, sel = route(p, u, cfg)
    one = jax.checkpoint(functools.partial(_expert, dtype=dtype))
    out, _ = lax.scan(lambda total, held: (total + one(u, *held), None), jnp.zeros_like(u),
                      (weights[:, lo:hi].T, p["w1"], p["w3"], p["w2"]))
    if shared and cfg.get("num_shared_experts"):
        out = out + swiglu(p["shared"], u, dtype)
    rows = jnp.sum(sel[:, :, None] == jnp.arange(lo, hi)[None, None, :], axis=(0, 1))
    return out.reshape(shape), rows


def _attention_sublayer(p, x, layer, cfg, product_dtype, no_gate, no_window):
    sliding = cfg["layer_types"][layer] == "sliding_attention"
    z = rms_norm(x, p["operator_norm"]["weight"], cfg["rms_norm_eps"])
    a = attention(p["operator"], z, cfg, sliding,
                  cfg["sliding_window"] if sliding and not no_window else None, product_dtype,
                  gate=not no_gate)
    return x + rms_norm(a, p["operator_out_norm"]["weight"], cfg["rms_norm_eps"])


def _ffn_sublayer(p, h, layer, cfg, product_dtype):
    u = rms_norm(h, p["ffn_norm"]["weight"], cfg["rms_norm_eps"])
    if layer < cfg["num_dense_layers"]:
        f, rows = swiglu(p["ffn"], u, product_dtype), None
    else:
        f, rows = experts(p["ffn"], u, cfg, product_dtype)
    return h + rms_norm(f, p["ffn_out_norm"]["weight"], cfg["rms_norm_eps"]), rows


def block(p, x, layer, cfg, product_dtype=None, no_gate=False, no_window=False, **_):
    """Layer ``layer``: ``(y, rows routed to the experts held, or None)``.  The
    two sublayers are rematerialised one after the other, so that the backward
    pass holds one's intermediates at a time."""
    h = jax.checkpoint(functools.partial(
        _attention_sublayer, layer=layer, cfg=cfg, product_dtype=product_dtype,
        no_gate=no_gate, no_window=no_window))(p, x)
    return jax.checkpoint(functools.partial(
        _ffn_sublayer, layer=layer, cfg=cfg, product_dtype=product_dtype))(p, h)


def hidden_states(params, tokens, cfg, **lower):
    """``(final normalised states, [rows per expert held] per expert layer)``."""
    x = params["embed"]["weight"][tokens]
    if cfg.get("mup_enabled") and not lower.get("no_embedding_scale"):
        x = x * jnp.sqrt(jnp.float32(cfg["hidden_size"]))
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
    return ("normal", shape)


def _shapes(cfg) -> dict:
    """The parameters' names and shapes from the configuration: ``("normal",
    shape)`` for a matrix, ``("embed", shape)`` for the token embedding,
    ``("one", shape)`` for a norm's weight, ``("bias", shape)`` for the
    selection bias."""
    d, heads, kv = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    head, f, routed = cfg["head_dim"], cfg["moe_intermediate_size"], _routed(cfg)
    lo, hi = _held(cfg)
    norm = lambda n: {"weight": ("one", (n,))}  # noqa: E731
    swiglu_of = lambda w: {"w1": {"weight": _matrix(w, d)}, "w3": {"weight": _matrix(w, d)},  # noqa: E731
                           "w2": {"weight": _matrix(d, w)}}
    blocks = []
    for i, _ in enumerate(cfg["layer_types"]):
        if i < cfg["num_dense_layers"]:
            ffn = swiglu_of(cfg["intermediate_size"])
        else:
            ffn = {"router": _matrix(d, routed), "expert_bias": ("bias", (routed,)),
                   "w1": _matrix(hi - lo, d, f), "w3": _matrix(hi - lo, d, f), "w2": _matrix(hi - lo, f, d)}
            if cfg.get("num_shared_experts"):
                ffn["shared"] = swiglu_of(f * cfg["num_shared_experts"])
        blocks.append({
            "operator_norm": norm(d),
            "operator": {"in_proj_weight": _matrix((heads + 2 * kv) * head, d),
                         "out_proj": {"weight": _matrix(d, heads * head)},
                         "q_norm": norm(head), "k_norm": norm(head),
                         "gate_proj": {"weight": _matrix(heads * head, d)}},
            "operator_out_norm": norm(d), "ffn_norm": norm(d), "ffn": ffn, "ffn_out_norm": norm(d)})
    return {"embed": {"weight": ("embed", (cfg["vocab_size"], d))}, "blocks": blocks, "norm": norm(d),
            "head": {"weight": _matrix(cfg["vocab_size"], d)}}


def init_params(key, cfg, init_std=0.02, bias_std=0.0, embed_std=None):
    """Float32 parameters from ``key``: every matrix ``N(0, init_std^2)``, the
    token embedding ``N(0, embed_std^2)`` (``None``: as the matrices), every
    norm's weight 1, the selection bias ``N(0, bias_std^2)``; one draw a leaf,
    keyed by the leaf's place in the flattened ``_shapes(cfg)``."""
    is_leaf = lambda x: isinstance(x, tuple)  # noqa: E731
    flat, treedef = jax.tree_util.tree_flatten(_shapes(cfg), is_leaf=is_leaf)

    def draw(i, kind, shape):
        if kind == "one":
            return jnp.ones(shape, jnp.float32)
        std = {"normal": init_std, "bias": bias_std, "embed": init_std if embed_std is None else embed_std}[kind]
        return std * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)

    return jax.tree_util.tree_unflatten(treedef, [draw(i, *leaf) for i, leaf in enumerate(flat)])


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
