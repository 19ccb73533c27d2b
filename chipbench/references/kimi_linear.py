"""A causal language model of Kimi Delta Attention, latent attention without
positions and sigmoid-routed experts beside a shared one (``model_type``
``kimi_linear``), written plainly.

Reference of the ``kimi_linear_48b_a3b_ep32`` configuration (job
``kimi_linear_train_step``) and of the CPU tests of ``heat_tpu.ops.chunk_kda``,
``nn.KimiDeltaAttention``, ``nn.LatentAttention`` and ``nn.models.PatternLM``.
It follows the public ``config.json`` of moonshotai/Kimi-Linear-48B-A3B-Instruct
and the Kimi Linear report (arXiv:2510.26692); what neither says is listed
under ``assumed`` in the configuration.  Everything is float32 with ``highest``
matmul precision; no ``heat_tpu`` import, no kernel, no cache.  Kimi Delta
Attention is its recurrence, token by token; attention is explicit scores; the
experts are a loop over the experts held with a dense mask over the tokens.

``z`` is a (sequences, positions, hidden) input, ``RMSNorm(x) = x /
sqrt(mean(x^2) + eps) * w``, no projection has a bias, a weight is stored
``(out, in)`` (an expert's ``(in, out)``, stacked over the experts held):

    block l       h = x + Op_l(RMSNorm(x));  y = h + FFN_l(RMSNorm(h))
    kda           [q, k, v] = silu(conv4(W_in z)) (a causal depthwise convolution a
                  channel, zero before the sequence's start), H heads of d;
                  q <- q / |q| / sqrt(d), k <- k / |k| (a head, eps 1e-6 under the root);
                  g = -exp(A_log) softplus(W_fb W_fa z + dt_bias) (a channel; A_log a head);
                  beta = sigmoid(W_b z) (a head);
                  S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T,
                  S_0 = 0;  o_t = S_t^T q_t;
                  out = W_o (RMSNorm_head(o) * sigmoid(W_gb W_ga z))
    mla           q = W_q z (H heads of nope + rope);  [c, k_pe] = W_kva z;
                  [k_nope, v] = W_kvb RMSNorm(c) (H heads);  k = [k_nope, k_pe], k_pe
                  the same for every head;  causal softmax(q k^T / sqrt(nope + rope)) v;
                  out = W_o.  No rotary position anywhere (mla_use_nope).
    dense FFN     W_2 (silu(W_1 z) * W_3 z)
    experts       s = sigmoid(z W_r);  sel = top_k(s + b);
                  w = s[sel] / (sum s[sel] + 1e-6) * routed_scaling_factor;
                  out = sum_{e in sel, e held} w_e E_e(z) + E_shared(z),  E a gated FFN
    ends          embedding, final RMSNorm, logits = h W_head^T (a matrix of its own),
                  loss = mean next-token cross-entropy

Departures from the published code, each for a reason: the selection is a
plain top-k over all experts (``num_expert_group`` 1 and ``topk_group`` 1 make
the grouped one the same); the low-rank gates have no bias but ``dt_bias``
(the published output gate has none either); the shared expert is added
whole on every rank, so an expert-parallel sum counts it once (``shared=``).

``experts_held`` (a range of expert ids) and the vocabulary are the
configuration's: the router always has ``num_experts`` outputs and picks
``num_experts_per_token``; what the experts not held would add is left out.

The functions take the parameters as the pytree ``PatternLM.init`` returns
(the same names and shapes), so gradients compare leaf by leaf, and
``init_params`` draws such a pytree from a key and the configuration's shapes
alone.  ``product_dtype`` rounds the operands of every matrix product to a
lower precision first: the control that a comparison must tell from the
reference itself.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

SEGMENT = 64  # tokens of the recurrence between two kept states


def _mm(a, b, dtype=None):
    """``a @ b`` in float32 at ``highest`` precision; with ``dtype`` the
    operands are rounded to it first."""
    if dtype is not None:
        a, b = a.astype(dtype), b.astype(dtype)
    return jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=lax.Precision.HIGHEST)


def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def causal_conv(x, taps):
    """``y_t = sum_j taps[:, j] x_{t-(L-1)+j}`` a channel, zero before the
    start: ``x`` (sequences, positions, channels), ``taps`` (channels, L)."""
    n_taps, length = taps.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (n_taps - 1, 0), (0, 0)))
    return sum(taps[:, j] * padded[:, j:j + length] for j in range(n_taps))


def delta_rule(q, k, v, g, beta, state=None):
    """The gated delta rule token by token.  ``q, k, g``: (positions, heads,
    d_k), ``v``: (positions, heads, d_v), ``beta``: (positions, heads), one
    sequence.  Returns ``(o (positions, heads, d_v), the final state (heads,
    d_k, d_v))``.  Segments of ``SEGMENT`` tokens are rematerialised, so the
    gradient keeps a state a segment and not a state a token."""
    length, heads, dk = k.shape
    if state is None:
        state = jnp.zeros((heads, dk, v.shape[-1]), jnp.float32)

    def token(s, x):
        qt, kt, vt, gt, bt = x
        s = s * jnp.exp(gt)[..., None]
        # what the state holds for this key, and the write that replaces it
        held = jnp.sum(kt[..., None] * s, axis=-2)
        s = s + (bt[:, None] * kt)[..., None] * (vt - held)[:, None, :]
        return s, jnp.sum(qt[..., None] * s, axis=-2)

    @jax.checkpoint
    def segment(s, xs):
        return lax.scan(token, s, xs)

    # zeros pad the last segment: such a token decays nothing (g = 0) and writes nothing (beta = 0)
    pad = -length % SEGMENT
    xs = [jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1)) for t in (q, k, v, g, beta)]
    state, o = lax.scan(segment, state, [t.reshape((-1, SEGMENT) + t.shape[1:]) for t in xs])
    return o.reshape((length + pad,) + o.shape[2:])[:length], state


def kda(p, z, cfg, dtype=None):
    lin = cfg["linear_attn_config"]
    heads, d = lin["num_heads"], lin["head_dim"]
    n, length, _ = z.shape
    qkv = jax.nn.silu(causal_conv(_mm(z, p["in_proj"]["weight"].T, dtype), p["conv"]["weight"]))
    q, k, v = (t.reshape(n, length, heads, d) for t in jnp.split(qkv, 3, axis=-1))
    q = q * lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) / math.sqrt(d)
    k = k * lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    f = _mm(_mm(z, p["f_a"]["weight"].T, dtype), p["f_b"]["weight"].T, dtype) + p["dt_bias"]
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(f).reshape(n, length, heads, d)
    beta = jax.nn.sigmoid(_mm(z, p["b_proj"]["weight"].T, dtype))
    o = lax.map(lambda t: delta_rule(*t)[0], (q, k, v, g, beta))  # a sequence at a time
    gate = jax.nn.sigmoid(_mm(_mm(z, p["g_a"]["weight"].T, dtype), p["g_b"]["weight"].T, dtype))
    o = rms_norm(o, p["o_norm"]["weight"], cfg["rms_norm_eps"]) * gate.reshape(o.shape)
    return _mm(o.reshape(n, length, heads * d), p["out_proj"]["weight"].T, dtype)


def _attend(q, k, v, dtype):
    """One head over one sequence: ``q, k`` (S, d_qk), ``v`` (S, d_v)."""
    s = _mm(q, k.T, dtype) / jnp.sqrt(jnp.float32(q.shape[-1]))
    length = s.shape[-1]
    causal = jnp.arange(length)[:, None] >= jnp.arange(length)[None, :]
    return _mm(jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1), v, dtype)


def mla(p, z, cfg, dtype=None):
    heads, nope, rope = cfg["num_attention_heads"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, rank = cfg["v_head_dim"], cfg["kv_lora_rank"]
    n, length, _ = z.shape
    q = _mm(z, p["q_proj"]["weight"].T, dtype).reshape(n, length, heads, nope + rope)
    c, k_pe = jnp.split(_mm(z, p["kv_a_proj"]["weight"].T, dtype), [rank], axis=-1)
    c = rms_norm(c, p["kv_a_norm"]["weight"], cfg["rms_norm_eps"])
    kv = _mm(c, p["kv_b_proj"]["weight"].T, dtype).reshape(n, length, heads, nope + dv)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe[:, :, None, :], (n, length, heads, rope))], axis=-1)
    flat = lambda t: jnp.moveaxis(t, 2, 1).reshape((n * heads, length, t.shape[-1]))  # noqa: E731
    one = jax.checkpoint(functools.partial(_attend, dtype=dtype))
    out = lax.map(lambda t: one(*t), (flat(q), flat(k), flat(kv[..., nope:])))
    out = jnp.moveaxis(out.reshape(n, heads, length, dv), 1, 2).reshape(n, length, heads * dv)
    return _mm(out, p["out_proj"]["weight"].T, dtype)


def dense_ffn(p, z, dtype=None):
    gate = jax.nn.silu(_mm(z, p["w1"]["weight"].T, dtype))
    return _mm(gate * _mm(z, p["w3"]["weight"].T, dtype), p["w2"]["weight"].T, dtype)


def route(p, z, cfg):
    """``(weights (tokens, experts) with zeros off the selection, selection
    (tokens, k))`` over all the experts."""
    s = jax.nn.sigmoid(_mm(z, p["router"]))
    _, sel = lax.top_k(s + lax.stop_gradient(p["expert_bias"]), cfg["num_experts_per_token"])
    picked = jnp.take_along_axis(s, sel, axis=-1)
    if cfg.get("moe_renormalize", True):
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-6)
    picked = picked * cfg.get("routed_scaling_factor", 1.0)
    onehot = jax.nn.one_hot(sel, cfg["num_experts"], dtype=jnp.float32)  # (tokens, k, E)
    return jnp.einsum("tk,tke->te", picked, onehot), sel


def _expert(z, w1, w3, w2, dtype):
    return _mm(jax.nn.silu(_mm(z, w1, dtype)) * _mm(z, w3, dtype), w2, dtype)


def experts(p, z, cfg, dtype=None, shared: bool = True):
    """``(the held experts' part of the layer's output plus, with ``shared``,
    the shared expert's, rows routed to each expert held)``."""
    lo, hi = cfg["experts_held"]
    shape = z.shape
    z = z.reshape(-1, shape[-1])
    weights, sel = route(p, z, cfg)
    one = jax.checkpoint(functools.partial(_expert, dtype=dtype))
    out = dense_ffn(p["shared"], z, dtype) if shared and "shared" in p else jnp.zeros_like(z)
    for e in range(lo, hi):
        out = out + weights[:, e:e + 1] * one(z, p["w1"][e - lo], p["w3"][e - lo], p["w2"][e - lo])
    rows = jnp.sum(sel[:, :, None] == jnp.arange(lo, hi)[None, None, :], axis=(0, 1))
    return out.reshape(shape), rows


def block(p, x, kind, cfg, product_dtype=None):
    """One layer: ``(y, rows routed to the experts held, or None)``."""
    z = rms_norm(x, p["operator_norm"]["weight"], cfg["rms_norm_eps"])
    h = x + {"kda": kda, "mla": mla}[kind](p["operator"], z, cfg, product_dtype)
    z = rms_norm(h, p["ffn_norm"]["weight"], cfg["rms_norm_eps"])
    if "router" in p["ffn"]:
        out, rows = experts(p["ffn"], z, cfg, product_dtype)
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
    """``(mean next-token cross-entropy, rows per expert layer)``."""
    h, rows = hidden_states(params, tokens, cfg, **lower)
    one = jax.checkpoint(functools.partial(
        _sequence_nll, head=params["head"]["weight"], dtype=lower.get("product_dtype")))
    total = jnp.sum(lax.map(lambda t: one(*t), (h, tokens)))
    n, length = tokens.shape
    return total / (n * (length - 1)), rows


def loss_and_grads(params, tokens, cfg, **lower):
    """``(loss, rows, gradients)``; the selection bias gets a zero gradient."""
    (value, rows), grads = jax.value_and_grad(loss, has_aux=True)(params, tokens, cfg, **lower)
    return value, rows, grads


def _matrix(*shape):
    return {"weight": ("normal", shape)}


def _shapes(cfg) -> dict:
    """The parameters' names and shapes from the configuration: ``("normal",
    shape)`` for a matrix, ``("one", shape)`` for a norm's weight, ``("bias",
    shape)`` for the selection bias, ``("a_log", shape)`` and ``("dt_bias",
    shape)`` for the decay's two vectors."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    lin, rank = cfg["linear_attn_config"], cfg["kda_gate_rank"]
    width = lin["num_heads"] * lin["head_dim"]
    norm = lambda n: {"weight": ("one", (n,))}  # noqa: E731
    swiglu = lambda f: {"w1": _matrix(f, d), "w3": _matrix(f, d), "w2": _matrix(d, f)}  # noqa: E731
    blocks = []
    for i, kind in enumerate(cfg["layer_types"]):
        if kind == "kda":
            operator = {
                "in_proj": _matrix(3 * width, d),
                "conv": _matrix(3 * width, lin["short_conv_kernel_size"]),
                "f_a": _matrix(rank, d), "f_b": _matrix(width, rank),
                "g_a": _matrix(rank, d), "g_b": _matrix(width, rank),
                "b_proj": _matrix(lin["num_heads"], d),
                "A_log": ("a_log", (lin["num_heads"],)), "dt_bias": ("dt_bias", (width,)),
                "o_norm": norm(lin["head_dim"]), "out_proj": _matrix(d, width)}
        else:
            nope, rope, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
            operator = {
                "q_proj": _matrix(heads * (nope + rope), d),
                "kv_a_proj": _matrix(cfg["kv_lora_rank"] + rope, d),
                "kv_a_norm": norm(cfg["kv_lora_rank"]),
                "kv_b_proj": _matrix(heads * (nope + dv), cfg["kv_lora_rank"]),
                "out_proj": _matrix(d, heads * dv)}
        if i < cfg["first_k_dense_replace"]:
            ffn = swiglu(cfg["intermediate_size"])
        else:
            f, routed = cfg["moe_intermediate_size"], cfg["num_experts"]
            lo, hi = cfg.get("experts_held") or (0, routed)
            ffn = {"router": ("normal", (d, routed)), "expert_bias": ("bias", (routed,)),
                   "w1": ("normal", (hi - lo, d, f)), "w3": ("normal", (hi - lo, d, f)),
                   "w2": ("normal", (hi - lo, f, d))}
            if cfg.get("num_shared_experts"):
                ffn["shared"] = swiglu(f * cfg["num_shared_experts"])
        blocks.append({"operator_norm": norm(d), "operator": operator, "ffn_norm": norm(d), "ffn": ffn})
    return {"embed": _matrix(cfg["vocab_size"], d), "blocks": blocks, "norm": norm(d),
            "head": _matrix(cfg["vocab_size"], d)}


def init_params(key, cfg, init_std=0.02, bias_std=0.0):
    """Float32 parameters from ``key``: every matrix ``N(0, init_std^2)``,
    every norm's weight 1, the selection bias ``N(0, bias_std^2)``, ``A_log =
    log U(1, 16)`` a head, ``dt_bias = softplus^-1(dt)`` with ``log dt ~
    U(log 0.001, log 0.1)`` a channel; one draw a leaf, keyed by the leaf's
    place in the flattened ``_shapes(cfg)``."""
    is_leaf = lambda x: isinstance(x, tuple)  # noqa: E731
    flat, treedef = jax.tree_util.tree_flatten(_shapes(cfg), is_leaf=is_leaf)

    def draw(i, kind, shape):
        k = jax.random.fold_in(key, i)
        if kind == "one":
            return jnp.ones(shape, jnp.float32)
        if kind == "a_log":
            return jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        if kind == "dt_bias":
            dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
            return dt + jnp.log(-jnp.expm1(-dt))  # softplus(dt_bias) = dt
        return (bias_std if kind == "bias" else init_std) * jax.random.normal(k, shape, jnp.float32)

    return jax.tree_util.tree_unflatten(treedef, [draw(i, *leaf) for i, leaf in enumerate(flat)])


def _names(path) -> list:
    return [str(getattr(k, "key", getattr(k, "idx", ""))) for k in path]


def decays(path) -> bool:
    """Weight decay on every matrix, the output head among them; none on a
    norm's weight, the selection bias, the embedding, ``A_log`` or ``dt_bias``."""
    names = _names(path)
    if names[0] == "embed" or names[-1] in ("expert_bias", "A_log", "dt_bias"):
        return False
    return not any(n.endswith("norm") for n in names)


def trainable(path) -> bool:
    return "expert_bias" not in _names(path)


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
    names = _names(path)
    if names[0] in ("embed", "head"):
        return {"embed": "embedding", "head": "head"}[names[0]]
    if "expert_bias" in names:
        return "selection_bias"
    if any(n.endswith("norm") for n in names):
        return "norms"
    if "router" in names:
        return "router"
    if names[2] == "ffn":
        if "shared" in names:
            return "shared_expert"
        return "experts" if len(names) == 4 else "dense_ffn"
    return f"operator_{names[1]}"


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
