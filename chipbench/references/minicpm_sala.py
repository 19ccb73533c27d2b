"""A causal language model of learned block-sparse attention (InfLLM-v2, the
``minicpm4`` mixer) beside decayed linear attention (Lightning Attention, the
``lightning-attn`` mixer), in MiniCPM's muP form (``model_type``
``minicpm_sala``), written plainly.

Reference of the ``minicpm_sala_9b_tp2`` configuration (job
``minicpm_sala_train_step``) and of the CPU tests of
``ops.sparse_attention``, ``ops.lightning``, ``nn.LightningAttention`` and
``nn.models.PatternLM(sparse=, residual_scale=, head_input_scale=, heads_held=,
ffn_held= ...)``.  It follows the public ``config.json`` of openbmb/MiniCPM-SALA;
what no key of it states is listed under ``assumed`` in the configuration's
file.  Everything is float32 with ``highest`` matmul precision; no ``heat_tpu``
import, no kernel, no cache.  Sparse attention is explicit masked scores over
the chosen blocks, lightning attention the token recurrence.

``x`` is a (sequences, positions, hidden) input, ``RMSNorm(x) = x /
sqrt(mean(x^2) + eps) * w``, no projection has a bias, a weight is stored
``(out, in)``, ``c = scale_depth / sqrt(published layers)``:

    start       h_0 = scale_emb E[tokens]
    layer l     z = RMSNorm_in(x)
      minicpm4  q, k, v = split(W_qkv z): H query heads, H_kv key/value heads of d
                q, k = RMSNorm_q(q), RMSNorm_k(k) a head; no positions
                K^c_j = mean(k[16 j : 16 j + 32]); P(i, j) = sum over a group's heads of
                softmax_j(q_i . K^c_j / sqrt(d)) over the j with 16 j + 31 <= i;
                score(i, m) = max of P(i, j) over the j whose keys overlap block m (64 keys);
                kept: block 0, the blocks holding i - 2047 .. i, then the highest scores
                (ties: the lower block) up to 64 blocks, none after i's
                a = concat(softmax over the kept keys <= i of q . k / sqrt(d), times v)
                h = x + c W_o (a * sigmoid(W_g z))
      lightning q, k, v = SiLU(split(W_in z)) as heads of d; q, k = RMSNorm a head, rotated
                (rotate-half, rope_theta); q /= sqrt(d)
                S_t = lambda_h S_{t-1} + k_t^T v_t, o_t = q_t S_t,  lambda_h = exp(-s_h),
                s_h = 2^(-8 (h + 1) / lightning_nh) (1 - l / (published layers - 1) + 1e-5)
                h = x + c W_o (RMSNorm_head(o) * sigmoid(W_g z))
                u = RMSNorm_ffn(h);  y = h + c W_2 (silu(W_1 u) * W_3 u)
    ends        logits = (RMSNorm(h) / (hidden_size / dim_model_base)) W_head^T,
                loss = mean next-token cross-entropy

A layer holds its share of heads and columns (``heads_held``,
``kv_heads_held``, ``lightning_heads_held``, ``ffn_held``: ranges; absent:
all): its output projection gives the share's partial sum, as a
tensor-parallel layer does before its all-reduce; a lightning head keeps its
global index for its decay.  The vocabulary is the configuration's.

The functions take the parameters as the pytree ``PatternLM.init`` returns
(the same names and shapes), so gradients compare leaf by leaf, and
``init_params`` draws such a pytree from a key and the configuration's shapes
alone.  Sparse attention and the selection take ``ROWS`` query rows at a time
and a head at a time, the SwiGLU ``ROWS`` rows at a time (a layer's float32
intermediates are 1 GiB each at 32,768 tokens), the recurrence ``CHUNK``
tokens a rematerialised piece,
the logits ``HEAD_ROWS`` rows at a time, a layer's two sublayers one after
the other, each rematerialised: that only bounds the memory, the numbers are
the same.  ``selection`` (one ``(sequences, kv heads, S, topk)`` array a
sparse layer) replaces the selection the reference makes by one given, as the
expert jobs replay a routing; ``product_dtype`` rounds the operands of every
matrix product to a lower precision first, ``no_decay`` runs lightning
attention without its decay, ``no_residual_scale`` adds the sublayers'
outputs unscaled: the controls that a comparison must tell from the
reference itself.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

ROWS = 1024  # query rows scored at a time
CHUNK = 256  # tokens of the recurrence a rematerialised piece
HEAD_ROWS = 4096  # rows of a sequence whose logits exist at a time
SPARSE, LIGHTNING = "minicpm4", "lightning-attn"


def _mm(a, b, dtype=None):
    """``a @ b`` in float32 at ``highest`` precision; with ``dtype`` the
    operands are rounded to it first."""
    if dtype is not None:
        a, b = a.astype(dtype), b.astype(dtype)
    return jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32), precision=lax.Precision.HIGHEST)


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


def _held(cfg, key, total):
    return tuple(cfg.get(key) or (0, total))


def residual(cfg) -> float:
    return cfg["scale_depth"] / math.sqrt(cfg.get("published", {}).get("num_hidden_layers", cfg["num_hidden_layers"]))


# ---------------------------------------------------------------------- #
# the selection and sparse attention, one key/value head
# ---------------------------------------------------------------------- #
def compressed_keys(k, sc):
    """``(Nc, d)``: the mean of each ``kernel_size`` keys every ``kernel_stride``."""
    n = (k.shape[0] - sc["kernel_size"]) // sc["kernel_stride"] + 1
    where = jnp.arange(n)[:, None] * sc["kernel_stride"] + jnp.arange(sc["kernel_size"])[None, :]
    return jnp.mean(k[where], axis=1)


def _scores_rows(q, rows, kc, sc, blocks, dtype):
    """``score(i, m)`` of a group's query rows ``q`` (g, R, d) at ``rows``,
    with the forced blocks at ``+inf`` and those after the row at ``-inf``."""
    n, d = kc.shape
    s = _mm(q, kc.T, dtype) / jnp.sqrt(jnp.float32(d))  # (g, R, Nc)
    past = (jnp.arange(n) * sc["kernel_stride"] + sc["kernel_size"] - 1)[None, :] <= rows[:, None]
    p = jax.nn.softmax(jnp.where(past, s, -jnp.inf), axis=-1)
    group = jnp.sum(jnp.where(past, p, 0.0), axis=0)  # (R, Nc); a row with no past key: 0
    size, stride = sc["block_size"], sc["kernel_stride"]
    m = jnp.arange(blocks)
    # the compressed keys overlapping block m: stride j < (m + 1) size and stride j + kernel > m size
    first = -(-(m * size - sc["kernel_size"] + 1) // stride)
    span = (size + sc["kernel_size"]) // stride - 1
    j = first[:, None] + jnp.arange(span)[None, :]  # (blocks, span)
    ok = (j >= 0) & (j < n)
    score = jnp.max(jnp.where(ok, group[:, jnp.clip(j, 0, n - 1)], 0.0), axis=-1)  # (R, blocks)
    own = rows[:, None] // size
    low = jnp.maximum(rows - sc["window_size"] + 1, 0)[:, None] // size
    forced = (m[None, :] < sc["init_blocks"]) | (m[None, :] >= low)
    return jnp.where(m[None, :] > own, -jnp.inf, jnp.where(forced, jnp.inf, score))


def _choose(score, topk):
    """The ``topk`` highest of each row (ties: the lower block), -1 for a
    block after the row."""
    order = jnp.argsort(-score, axis=-1, stable=True)[:, :topk]
    return jnp.where(jnp.take_along_axis(score, order, axis=-1) > -jnp.inf, order, -1)


def _attend_rows(q, rows, k, v, chosen, size, dtype):
    """Query rows ``rows`` of one head over the keys of their ``chosen``
    blocks (R, topk) at or before them (``chosen`` None: every key)."""
    length = k.shape[0]
    kept = jnp.arange(length)[None, :] <= rows[:, None]
    if chosen is not None:
        blocks = jnp.any(chosen[:, :, None] == jnp.arange(length // size)[None, None, :], axis=1)
        kept = kept & jnp.repeat(blocks, size, axis=1)
    s = _mm(q, k.T, dtype) / jnp.sqrt(jnp.float32(q.shape[-1]))
    return _mm(jax.nn.softmax(jnp.where(kept, s, -jnp.inf), axis=-1), v, dtype)


def sparse_group(q, k, v, sc, selection=None, dtype=None):
    """One key/value head: ``q`` (g, S, d), ``k``, ``v`` (S, d) to ``(out (g,
    S, d), facts)``.  ``facts``: ``chosen`` (S, topk) the blocks used,
    ``own`` the reference's own choice (-1 where a row has fewer blocks),
    ``forced`` which of those it must keep, ``kth`` its lowest chosen score
    a row (``-inf`` where it keeps every block at or before the row),
    ``picked`` its scores of the blocks used (``+inf`` for a forced one,
    ``-inf`` for one after the row)."""
    length = k.shape[0]
    blocks, topk = length // sc["block_size"], min(sc["topk"], length // sc["block_size"])
    kc = compressed_keys(lax.stop_gradient(k), sc)
    q_sel = lax.stop_gradient(q)
    step = min(ROWS, length)

    def select(t):
        qs, rows = t
        score = _scores_rows(qs, rows, kc, sc, blocks, dtype)
        own = _choose(score, topk)
        kept = jnp.take_along_axis(score, jnp.maximum(own, 0), axis=-1)
        kth = jnp.min(jnp.where(own >= 0, kept, jnp.inf), axis=-1)
        kth = jnp.where(jnp.all(own >= 0, axis=-1), kth, -jnp.inf)
        return score, own, kth, (own >= 0) & (kept == jnp.inf)

    rows = jnp.arange(length).reshape(-1, step)
    by_rows = jnp.moveaxis(q_sel.reshape(q.shape[0], -1, step, q.shape[-1]), 1, 0)
    score, own, kth, forced = lax.map(select, (by_rows, rows))
    score, own, kth = score.reshape(length, blocks), own.reshape(length, topk), kth.reshape(length)
    chosen = own if selection is None else selection
    picked = jnp.where(chosen >= 0, jnp.take_along_axis(score, jnp.maximum(chosen, 0), axis=-1), jnp.inf)
    one = jax.checkpoint(functools.partial(_attend_rows, size=sc["block_size"], dtype=dtype))

    def head(qh):
        parts = lax.map(lambda t: one(t[0], t[1], k, v, t[2]),
                        (qh.reshape(-1, step, qh.shape[-1]), rows, chosen.reshape(-1, step, topk)))
        return parts.reshape(length, -1)

    return lax.map(head, q), {"chosen": chosen, "own": own, "kth": kth, "picked": picked,
                              "forced": forced.reshape(length, topk)}


def sparse_attention(p, z, cfg, selection=None, dtype=None, **_):
    """The ``minicpm4`` sublayer's operator on the normalised input ``z``:
    ``(output before the residual scale, facts a key/value head)``."""
    d, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    lo, hi = _held(cfg, "heads_held", cfg["num_attention_heads"])
    klo, khi = _held(cfg, "kv_heads_held", cfg["num_key_value_heads"])
    heads, kv_heads = hi - lo, khi - klo
    n, length, _ = z.shape
    w = p["in_proj_weight"]
    split = lambda t, m: jnp.moveaxis(t.reshape(n, length, m, d), 2, 1)  # noqa: E731
    q = split(_mm(z, w[:heads * d].T, dtype), heads)
    k = split(_mm(z, w[heads * d:(heads + kv_heads) * d].T, dtype), kv_heads)
    v = split(_mm(z, w[(heads + kv_heads) * d:].T, dtype), kv_heads)
    q, k = rms_norm(q, p["q_norm"]["weight"], eps), rms_norm(k, p["k_norm"]["weight"], eps)
    group = heads // kv_heads
    q = q.reshape(n, kv_heads, group, length, d)
    sc = cfg["sparse_config"]
    if length <= sc["dense_len"]:  # dense: every earlier key
        step = min(ROWS, length) if length % min(ROWS, length) == 0 else length
        rows = jnp.arange(length).reshape(-1, step)
        one = jax.checkpoint(functools.partial(_attend_rows, chosen=None, size=1, dtype=dtype))
        causal = lambda qh, kh, vh: lax.map(  # noqa: E731
            lambda t: one(t[0], t[1], kh, vh), (qh.reshape(-1, step, d), rows)).reshape(length, d)
        out = jax.vmap(jax.vmap(lambda qg, kh, vh: lax.map(lambda qh: causal(qh, kh, vh), qg)))(q, k, v)
        facts = None
    elif selection is None:
        out, facts = jax.vmap(jax.vmap(functools.partial(sparse_group, sc=sc, dtype=dtype)))(q, k, v)
    else:
        out, facts = jax.vmap(jax.vmap(
            lambda a, b, c, s: sparse_group(a, b, c, sc, selection=s, dtype=dtype)))(q, k, v, selection)
    out = jnp.moveaxis(out.reshape(n, heads, length, d), 1, 2).reshape(n, length, heads * d)
    out = out * jax.nn.sigmoid(_mm(z, p["gate_proj"]["weight"].T, dtype))
    return _mm(out, p["out_proj"]["weight"].T, dtype), facts


# ---------------------------------------------------------------------- #
# lightning attention
# ---------------------------------------------------------------------- #
def slopes(cfg, layer):
    total, layers = cfg["lightning_nh"], cfg.get("published", {}).get("num_hidden_layers", cfg["num_hidden_layers"])
    lo, hi = _held(cfg, "lightning_heads_held", total)
    depth = 1.0 - layer / (layers - 1) + 1e-5
    return jnp.asarray([2.0 ** (-8.0 * (h + 1) / total) * depth for h in range(lo, hi)], jnp.float32)


def recurrence(q, k, v, decay):
    """``o_t = q_t S_t``, ``S_t = decay S_{t-1} + k_t^T v_t`` token by token:
    ``q, k`` (H, S, d_k), ``v`` (H, S, d_v), ``decay`` (H,)."""
    length = q.shape[1]
    step = min(CHUNK, length)
    if length % step:
        step = length

    def token(state, t):
        qt, kt, vt = t
        state = decay[:, None, None] * state + kt[:, :, None] * vt[:, None, :]
        return state, jnp.einsum("hd,hdv->hv", qt, state, precision=lax.Precision.HIGHEST)

    @jax.checkpoint
    def piece(state, t):
        return lax.scan(token, state, t)

    pieces = lambda a: jnp.moveaxis(a, 1, 0).reshape(-1, step, *a.shape[:1], a.shape[-1])  # noqa: E731
    zero = jnp.zeros((q.shape[0], q.shape[-1], v.shape[-1]), jnp.float32)
    _, o = lax.scan(piece, zero, (pieces(q), pieces(k), pieces(v)))
    return jnp.moveaxis(o.reshape(length, q.shape[0], -1), 0, 1)


def lightning(p, z, cfg, layer, dtype=None, no_decay=False, **_):
    """The ``lightning-attn`` sublayer's operator on the normalised input
    ``z``, before the residual scale."""
    d, eps = cfg["lightning_head_dim"], cfg["rms_norm_eps"]
    lo, hi = _held(cfg, "lightning_heads_held", cfg["lightning_nh"])
    heads = hi - lo
    n, length, _ = z.shape
    proj = jax.nn.silu(_mm(z, p["in_proj"]["weight"].T, dtype))
    q, k, v = (jnp.moveaxis(t.reshape(n, length, heads, d), 2, 1) for t in jnp.split(proj, 3, axis=-1))
    if cfg.get("qk_norm"):
        q, k = rms_norm(q, p["q_norm"]["weight"], eps), rms_norm(k, p["k_norm"]["weight"], eps)
    if cfg.get("lightning_use_rope"):
        pos = jnp.arange(length)
        q, k = rotate_half(q, pos, cfg["rope_theta"]), rotate_half(k, pos, cfg["rope_theta"])
    q = q / jnp.sqrt(jnp.float32(d))
    decay = jnp.ones((heads,)) if no_decay else jnp.exp(-slopes(cfg, layer))
    o = jax.vmap(lambda a, b, c: recurrence(a, b, c, decay))(q, k, v)  # (n, H, S, d)
    o = rms_norm(o, 1.0, eps)
    o = jnp.moveaxis(o, 1, 2).reshape(n, length, heads * d) * p["o_norm"]["weight"]
    o = o * jax.nn.sigmoid(_mm(z, p["gate_proj"]["weight"].T, dtype))
    return _mm(o, p["out_proj"]["weight"].T, dtype)


def _swiglu_rows(u, w1, w3, w2, dtype):
    return _mm(jax.nn.silu(_mm(u, w1.T, dtype)) * _mm(u, w3.T, dtype), w2.T, dtype)


def swiglu(p, u, dtype=None):
    """``W_2 (silu(W_1 u) * W_3 u)``, ``ROWS`` rows at a time."""
    rows = u.reshape(-1, u.shape[-1])
    step = ROWS if rows.shape[0] % ROWS == 0 else rows.shape[0]
    one = jax.checkpoint(functools.partial(
        _swiglu_rows, w1=p["w1"]["weight"], w3=p["w3"]["weight"], w2=p["w2"]["weight"], dtype=dtype))
    return lax.map(one, rows.reshape(-1, step, rows.shape[-1])).reshape(u.shape)


def _operator_sublayer(p, x, layer, cfg, selection, lower):
    c = 1.0 if lower.get("no_residual_scale") else residual(cfg)
    z = rms_norm(x, p["operator_norm"]["weight"], cfg["rms_norm_eps"])
    dtype = lower.get("product_dtype")
    if cfg["mixer_types"][layer] == SPARSE:
        a, facts = sparse_attention(p["operator"], z, cfg, selection=selection, dtype=dtype)
    else:
        a, facts = lightning(p["operator"], z, cfg, layer, dtype=dtype, no_decay=lower.get("no_decay")), None
    return x + c * a, facts


def _ffn_sublayer(p, h, cfg, lower):
    c = 1.0 if lower.get("no_residual_scale") else residual(cfg)
    u = rms_norm(h, p["ffn_norm"]["weight"], cfg["rms_norm_eps"])
    return h + c * swiglu(p["ffn"], u, lower.get("product_dtype"))


def block(p, x, layer, cfg, selection=None, **lower):
    """Layer ``layer``: ``(y, the sparse layer's facts or None)``; the two
    sublayers rematerialised one after the other."""
    h, facts = jax.checkpoint(functools.partial(
        _operator_sublayer, layer=layer, cfg=cfg, lower=lower))(p, x, selection=selection)
    return jax.checkpoint(functools.partial(_ffn_sublayer, cfg=cfg, lower=lower))(p, h), facts


def hidden_states(params, tokens, cfg, selection=None, **lower):
    """``(the last layer's output, [facts] a sparse layer)``; ``selection``:
    a list, one a sparse layer, or ``None``."""
    x = params["embed"]["weight"][tokens] * cfg["scale_emb"]
    facts, sparse = [], 0
    for layer, p in enumerate(params["blocks"]):
        given = None
        if cfg["mixer_types"][layer] == SPARSE and selection is not None:
            given, sparse = selection[sparse], sparse + 1
        x, f = jax.checkpoint(functools.partial(block, layer=layer, cfg=cfg, **lower))(p, x, selection=given)
        if f is not None:
            facts.append(f)
    return x, facts


def head_input(x, w, cfg):
    """The final norm, divided by ``hidden_size / dim_model_base``."""
    return rms_norm(x, w, cfg["rms_norm_eps"]) / (cfg["hidden_size"] / cfg["dim_model_base"])


def _rows_nll(x, targets, counts, w, head, cfg, dtype):
    """Summed negative log-likelihood of the rows that count, from the last
    layer's output ``x``."""
    lg = _mm(head_input(x, w, cfg), head.T, dtype)
    picked = jnp.take_along_axis(lg, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(counts, jax.nn.logsumexp(lg, axis=-1) - picked, 0.0))


def logits(params, tokens, cfg, **lower):
    x, _ = hidden_states(params, tokens, cfg, **lower)
    return _mm(head_input(x, params["norm"]["weight"], cfg), params["head"]["weight"].T, lower.get("product_dtype"))


def loss(params, tokens, cfg, selection=None, **lower):
    """``(mean next-token cross-entropy, [facts] a sparse layer)``."""
    x, facts = hidden_states(params, tokens, cfg, selection=selection, **lower)
    n, length = tokens.shape
    step = HEAD_ROWS if length % HEAD_ROWS == 0 else length
    # position t predicts token t + 1; a sequence's last position predicts nothing
    targets = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    counts = jnp.broadcast_to(jnp.arange(length) < length - 1, (n, length))
    # the final norm inside the rematerialised rows: its (S, hidden) output is not kept
    one = jax.checkpoint(functools.partial(
        _rows_nll, w=params["norm"]["weight"], head=params["head"]["weight"], cfg=cfg,
        dtype=lower.get("product_dtype")))
    blocks = lambda a: a.reshape((-1, step) + a.shape[2:])  # noqa: E731
    total = jnp.sum(lax.map(lambda t: one(*t), (blocks(x), blocks(targets), blocks(counts))))
    return total / (n * (length - 1)), facts


def loss_and_grads(params, tokens, cfg, selection=None, **lower):
    """``(loss, facts, gradients)``."""
    (value, facts), grads = jax.value_and_grad(loss, has_aux=True)(params, tokens, cfg, selection, **lower)
    return value, facts, grads


def _shapes(cfg) -> dict:
    """The parameters' names and shapes from the configuration: ``("normal",
    shape)`` for a matrix, ``("one", shape)`` for a norm's weight."""
    d, head = cfg["hidden_size"], cfg["head_dim"]
    lo, hi = _held(cfg, "heads_held", cfg["num_attention_heads"])
    klo, khi = _held(cfg, "kv_heads_held", cfg["num_key_value_heads"])
    llo, lhi = _held(cfg, "lightning_heads_held", cfg["lightning_nh"])
    flo, fhi = _held(cfg, "ffn_held", cfg["intermediate_size"])
    q, kv, lp, f = (hi - lo) * head, (khi - klo) * head, (lhi - llo) * cfg["lightning_head_dim"], fhi - flo
    norm = lambda n: {"weight": ("one", (n,))}  # noqa: E731
    matrix = lambda *shape: {"weight": ("normal", shape)}  # noqa: E731
    blocks = []
    for kind in cfg["mixer_types"]:
        if kind == SPARSE:
            op = {"in_proj_weight": ("normal", (q + 2 * kv, d)), "out_proj": matrix(d, q),
                  "q_norm": norm(head), "k_norm": norm(head), "gate_proj": matrix(q, d)}
        else:
            op = {"in_proj": matrix(3 * lp, d), "gate_proj": matrix(lp, d), "out_proj": matrix(d, lp),
                  "o_norm": norm(lp)}
            if cfg.get("qk_norm"):
                op.update(q_norm=norm(cfg["lightning_head_dim"]), k_norm=norm(cfg["lightning_head_dim"]))
        blocks.append({"operator_norm": norm(d), "operator": op, "ffn_norm": norm(d),
                       "ffn": {"w1": matrix(f, d), "w3": matrix(f, d), "w2": matrix(d, f)}})
    return {"embed": {"weight": ("normal", (cfg["vocab_size"], d))}, "blocks": blocks, "norm": norm(d),
            "head": matrix(cfg["vocab_size"], d)}


def init_params(key, cfg, init_std=0.02):
    """Float32 parameters from ``key``: every matrix ``N(0, init_std^2)``, the
    token embedding among them, every norm's weight 1; one draw a leaf, keyed
    by the leaf's place in the flattened ``_shapes(cfg)``."""
    is_leaf = lambda x: isinstance(x, tuple)  # noqa: E731
    flat, treedef = jax.tree_util.tree_flatten(_shapes(cfg), is_leaf=is_leaf)

    def draw(i, kind, shape):
        if kind == "one":
            return jnp.ones(shape, jnp.float32)
        return init_std * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)

    return jax.tree_util.tree_unflatten(treedef, [draw(i, *leaf) for i, leaf in enumerate(flat)])


def _names(path) -> list:
    return [str(getattr(k, "key", getattr(k, "idx", ""))) for k in path]


def decays(path) -> bool:
    """Weight decay on every matrix, the output head among them; none on a
    norm's weight or on the embedding."""
    names = _names(path)
    return not ("embed" in names or any(n.endswith("norm") for n in names))


def adamw_init(params):
    return {"m": jax.tree.map(jnp.zeros_like, params), "v": jax.tree.map(jnp.zeros_like, params),
            "t": jnp.zeros((), jnp.int32)}


def adamw_step(params, grads, state, *, lr, b1, b2, eps, weight_decay, warmup_steps=0):
    """Loshchilov and Hutter's AdamW with bias correction, decoupled decay
    ``lr * weight_decay * p`` on the leaves ``decays`` names.  With
    ``warmup_steps`` the ``t``-th step (counting from 1) uses ``lr * min(1, t
    / warmup_steps)``."""
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
    if names[2] == "operator":
        return f"operator_{names[1]}"
    return f"ffn_{names[1]}"


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
