"""Learned block-sparse attention (``ops.sparse_attention``: the selection and
its kernel), lightning attention (``ops.lightning``, ``nn.LightningAttention``)
and the layers that hold a tensor-parallel share of a wider one, each against
the benchmark's plain reference (``chipbench/references/minicpm_sala.py``) at
a small size on the CPU (the kernels under the interpreter); then a whole
``PatternLM`` in MiniCPM-SALA's layout: loss, gradients by group and three
``DataParallel`` AdamW steps through ``forward=model.next_token_loss``."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import heat_tpu as ht  # noqa: E402
from chipbench.references import minicpm_sala as ref  # noqa: E402
from heat_tpu.ops.flash_attention import _dense_attention  # noqa: E402
from heat_tpu.nn.linear_attention import lightning_slopes  # noqa: E402
from heat_tpu.nn.models import PatternLM  # noqa: E402
from heat_tpu.nn.modules import SwiGLU  # noqa: E402
from heat_tpu.ops import lightning as la  # noqa: E402
from heat_tpu.ops import sparse_attention as sa  # noqa: E402

SPARSE = {"kernel_size": 8, "kernel_stride": 4, "init_blocks": 1, "block_size": 16, "window_size": 32,
          "topk": 5, "dense_len": 64}
# 4 query heads and 2 key/value heads of 16, 4 lightning heads of 16, on a 48-wide model; the
# pattern of published layers 0 to 3; a rank holds the first half of every width
CFG = {
    "hidden_size": 48, "head_dim": 16, "num_attention_heads": 4, "num_key_value_heads": 2,
    "lightning_nh": 4, "lightning_head_dim": 16, "intermediate_size": 96, "vocab_size": 96,
    "mixer_types": ["minicpm4", "lightning-attn", "lightning-attn", "lightning-attn"], "num_hidden_layers": 4,
    "published": {"num_hidden_layers": 32}, "qk_norm": True, "lightning_use_rope": True, "rope_theta": 10000,
    "rms_norm_eps": 1e-6, "scale_emb": 12, "scale_depth": 1.4, "dim_model_base": 12, "sparse_config": SPARSE,
}
HALF = {**CFG, "heads_held": [0, 2], "kv_heads_held": [0, 1], "lightning_heads_held": [0, 2], "ffn_held": [0, 48]}
ADAMW = dict(lr=3e-4, b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1)
# float32 against float32 at ``highest`` precision: what is left is the order of the sums
TOL = 2e-4
LENGTH = 128  # past dense_len: every token from 80 on has more blocks than it keeps


def build(cfg=HALF, **kw):
    held = lambda key, n: range(*cfg.get(key, (0, n)))  # noqa: E731
    return PatternLM(
        cfg["vocab_size"], cfg["hidden_size"],
        ["sparse_attention" if k == "minicpm4" else "lightning" for k in cfg["mixer_types"]],
        num_heads=cfg["num_attention_heads"], num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        qk_norm=True, rope_kinds=("lightning",), rope_base=cfg["rope_theta"], ffn_dim=cfg["intermediate_size"],
        norm_eps=cfg["rms_norm_eps"], tie_embedding=False, attention_gate=True, embedding_scale=cfg["scale_emb"],
        sparse=sa.SparseConfig(**SPARSE), lightning_heads=cfg["lightning_nh"],
        lightning_head_dim=cfg["lightning_head_dim"], lightning_chunk=16, published_layers=32,
        heads_held=held("heads_held", 4), kv_heads_held=held("kv_heads_held", 2),
        lightning_heads_held=held("lightning_heads_held", 4), ffn_held=held("ffn_held", 96),
        residual_scale=1.4 / 32 ** 0.5, head_input_scale=cfg["dim_model_base"] / cfg["hidden_size"], **kw)


def close(got, want, tol=TOL):
    scale = max(float(jnp.max(jnp.abs(want))), 1e-6)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol * scale, rtol=0)


def _both(fn, *args):
    """``(fn(*args) (its first part if a tuple), its gradients under a fixed
    cotangent)``, one program."""
    def scalar(*a):
        out = fn(*a)
        out = out[0] if isinstance(out, tuple) else out
        return jnp.sum(out * jax.random.normal(jax.random.key(9), out.shape)), out

    (_, out), grads = jax.jit(jax.value_and_grad(scalar, argnums=tuple(range(len(args))), has_aux=True))(*args)
    return out, grads


def _qkv(length=LENGTH, d=16, g=2, kv=2, batch=2, key=0):
    keys = jax.random.split(jax.random.key(key), 3)
    q = jax.random.normal(keys[0], (batch, kv * g, length, d))
    k = jax.random.normal(keys[1], (batch, kv, length, d))
    v = jax.random.normal(keys[2], (batch, kv, length, d))
    return q, k, v


def _reference_choice(q, k, cfg=SPARSE):
    """The reference's own blocks, ``(B, H_kv, S, topk)``."""
    b, hk, length, d = k.shape
    grouped = q.reshape(b, hk, -1, length, d)
    return jax.vmap(jax.vmap(lambda qg, kh: ref.sparse_group(qg, kh, kh, cfg)[1]["own"]))(grouped, k)


# ---------------------------------------------------------------------- #
# the selection
# ---------------------------------------------------------------------- #
def test_the_selection_is_the_references():
    q, k, _ = _qkv()
    with jax.default_matmul_precision("highest"):
        sel, union, counts, kept = jax.jit(lambda q, k: sa.select(q.reshape(2, 2, 2, LENGTH, 16), k,
                                                                  sa.SparseConfig(**SPARSE)))(q, k)
        want = _reference_choice(q, k)
    np.testing.assert_array_equal(jnp.sort(sel, -1), jnp.sort(want, -1))
    rows = jnp.arange(LENGTH)
    # forced: block 0 and the window's blocks; never one after the row
    own = rows // 16
    assert bool(jnp.all(jnp.any(sel == 0, -1)))
    assert bool(jnp.all(jnp.any(sel == own[:, None], -1)))
    low = jnp.maximum(rows - 32 + 1, 0) // 16
    for m in range(LENGTH // 16):
        in_window = (m >= low) & (m <= own)
        assert bool(jnp.all(jnp.any(sel == m, -1) | ~in_window))
    assert bool(jnp.all(sel <= own[:, None]))
    # a row whose blocks all fit keeps every one, the rest -1
    fits = own + 1 <= 5
    assert bool(jnp.all(jnp.where(fits[:, None], (sel >= 0).sum(-1, keepdims=True) == own[:, None] + 1, True)))
    assert bool(jnp.all(jnp.where(~fits[:, None], sel >= 0, True)))
    # a tile's union is its tokens' blocks, sorted, and kept counts the pairs
    for t in range(LENGTH // 8):
        blocks = np.unique(np.asarray(sel[0, 1, 8 * t:8 * t + 8]))
        blocks = blocks[blocks >= 0]
        assert int(counts[0, 1, t]) == len(blocks)
        np.testing.assert_array_equal(union[0, 1, t, :len(blocks)], blocks)
    pairs = sum(int(sa.kept_pairs(sel[b, h], rows, 16)) for b in range(2) for h in range(2))
    assert int(kept) == pairs < 4 * LENGTH * (LENGTH + 1) // 2


def test_ties_go_to_the_lower_block():
    """Keys that are the same at every position: every compressed key scores
    alike, so a row's free choices are its lowest eligible blocks."""
    q, _, _ = _qkv()
    k = jnp.ones((2, 2, LENGTH, 16))
    cfg = sa.SparseConfig(**SPARSE)
    sel = jax.jit(lambda q, k: sa.select(q.reshape(2, 2, 2, LENGTH, 16), k, cfg)[0])(q, k)
    want = _reference_choice(q, k)
    np.testing.assert_array_equal(sel, jnp.where(want >= 0, want, -1))
    # row 127: block 0, the window's blocks 6 and 7, then the lowest of the rest, 1 and 2
    assert sorted(np.asarray(sel[0, 0, 127]).tolist()) == [0, 1, 2, 6, 7]


@pytest.mark.parametrize("row, blocks", [(0, [0]), (15, [0]), (16, [0, 1]), (31, [0, 1]), (47, [0, 1, 2])],
                         ids=["first", "end_of_block", "second_block", "in_window", "window_only"])
def test_positions_under_one_block_and_under_the_window(row, blocks):
    q, k, _ = _qkv()
    sel = sa.select(q.reshape(2, 2, 2, LENGTH, 16), k, sa.SparseConfig(**SPARSE))[0]
    got = sorted(int(b) for b in np.asarray(sel[1, 0, row]) if b >= 0)
    assert got == blocks


@pytest.mark.parametrize("length", [64, 96], ids=["at_dense_len", "past_dense_len"])
def test_both_sides_of_dense_len(length):
    q, k, v = _qkv(length)
    before = dict(sa.path_counts)
    with jax.default_matmul_precision("highest"):
        out, stats = jax.jit(lambda q, k, v: sa.sparse_attention(q, k, v, sa.SparseConfig(**SPARSE)))(q, k, v)
        causal = _dense_attention(q, jnp.repeat(k, 2, 1), jnp.repeat(v, 2, 1), True, 16 ** -0.5, length)
    triangle = 4 * length * (length + 1) // 2
    assert int(stats["causal_pairs"]) == triangle
    if length <= SPARSE["dense_len"]:
        assert sa.path_counts["flash"] == before["flash"] + 1 and "selection" not in stats
        assert int(stats["kept_pairs"]) == triangle
        close(out, causal)
    else:
        assert sa.path_counts["pallas"] == before["pallas"] + 1 and stats["selection"].shape == (2, 2, length, 5)
        assert int(stats["kept_pairs"]) < triangle
        assert float(jnp.max(jnp.abs(out - causal))) > 1e-3


# ---------------------------------------------------------------------- #
# the kernels
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("d", [16, 128], ids=["narrow", "lane_tile"])
def test_the_sparse_kernel_matches_the_reference(d):
    """Forward and gradients of the interpreted kernel and of the dense form,
    against the reference's masked attention on the same selection."""
    q, k, v = _qkv(d=d)
    cfg = sa.SparseConfig(**SPARSE)
    with jax.default_matmul_precision("highest"):
        sel = jax.jit(lambda q, k: sa.select(q.reshape(2, 2, 2, LENGTH, d), k, cfg)[0])(q, k)

        def want(q, k, v):
            grouped = q.reshape(2, 2, 2, LENGTH, d)
            out = jax.vmap(jax.vmap(lambda a, b, c, s: ref.sparse_group(a, b, c, SPARSE, selection=s)[0]))(
                grouped, k, v, sel)
            return out.reshape(q.shape)

        reference, d_ref = _both(want, q, k, v)
        before = dict(sa.path_counts)
        got, d_got = _both(lambda q, k, v: sa.sparse_attention(q, k, v, cfg), q, k, v)
        assert sa.path_counts["pallas"] == before["pallas"] + 1
        close(got, reference)
        jax.tree.map(close, d_got, d_ref)


def test_the_dense_form_is_the_kernel(monkeypatch):
    q, k, v = _qkv()
    cfg = sa.SparseConfig(**SPARSE)
    kernel, d_kernel = _both(lambda q, k, v: sa.sparse_attention(q, k, v, cfg), q, k, v)
    monkeypatch.setattr(sa, "_INTERPRET_MAX", 0)
    before = dict(sa.path_counts)
    dense, d_dense = _both(lambda q, k, v: sa.sparse_attention(q, k, v, cfg), q, k, v)
    assert sa.path_counts["dense"] == before["dense"] + 1
    close(dense, kernel, 1e-5)
    jax.tree.map(lambda a, b: close(a, b, 1e-5), d_dense, d_kernel)


@pytest.mark.parametrize("d, length", [(16, 128), (128, 49152)], ids=["narrow_heads", "past_vmem"])
def test_the_tpu_refuses_what_the_kernels_cannot_take(monkeypatch, d, length):
    """On the TPU a shape the kernels cannot take is an error, not the dense
    form, whose (S, S) mask is largest at exactly those lengths; traced only."""
    monkeypatch.setattr(sa, "platform_of", lambda _: "tpu")
    cfg = sa.SparseConfig(**SPARSE) if d == 16 else sa.SparseConfig()
    q = jax.ShapeDtypeStruct((1, 2, length, d), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 1, length, d), jnp.bfloat16)
    before = dict(sa.path_counts)
    with pytest.raises(ValueError, match="whole lane tiles" if d == 16 else "VMEM"):
        jax.eval_shape(lambda q, k, v: sa.sparse_attention(q, k, v, cfg), q, kv, kv)
    assert sa.path_counts == before


@pytest.mark.parametrize("d, length, chunk", [(128, 256, 64), (16, 100, 16)], ids=["kernels", "xla_ragged"])
def test_lightning_matches_the_token_recurrence(d, length, chunk):
    keys = jax.random.split(jax.random.key(3), 3)
    q, k, v = (0.3 * jax.random.normal(kk, (2, 2, length, d)) for kk in keys)
    log_decay = -jnp.asarray([0.8, 0.01])
    before = dict(la.path_counts)
    with jax.default_matmul_precision("highest"):
        got, d_got = _both(lambda q, k, v: la.lightning_attention(q, k, v, log_decay, chunk=chunk), q, k, v)
        want, d_want = _both(lambda q, k, v: jax.vmap(lambda a, b, c: ref.recurrence(a, b, c, jnp.exp(log_decay)))(
            q, k, v), q, k, v)
    assert la.path_counts["pallas" if d == 128 else "dense"] == before["pallas" if d == 128 else "dense"] + 1
    close(got, want)
    jax.tree.map(close, d_got, d_want)


# ---------------------------------------------------------------------- #
# the layers, whole and as two tensor-parallel shares
# ---------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def setup():
    with jax.default_matmul_precision("highest"):
        model = build(CFG)
        # matrices larger than at the published widths, so that every layer's output has the
        # size of the stream; norm weights off 1
        params = ref.init_params(jax.random.key(0), CFG, init_std=0.2)
        params = jax.tree_util.tree_map_with_path(
            lambda path, a: a + 0.1 * jnp.sin(jnp.arange(a.size, dtype=jnp.float32)).reshape(a.shape)
            if any(str(getattr(k, "key", "")).endswith("norm") for k in path) else a, params)
        tokens = jax.random.randint(jax.random.key(1), (2, LENGTH), 0, CFG["vocab_size"])
        return model, params, tokens


def test_the_layers_match_the_reference(setup):
    model, params, _ = setup
    z = jax.random.normal(jax.random.key(2), (2, LENGTH, 48))
    with jax.default_matmul_precision("highest"):
        sparse, p = model.blocks[0].operator, params["blocks"][0]["operator"]
        assert sparse.gate and sparse.qk_norm and not sparse.rope and sparse.sparse.topk == 5
        got, d_got = _both(lambda p, z: sparse.apply(p, z, causal=True), p, z)
        want, d_want = _both(lambda p, z: ref.sparse_attention(p, z, CFG)[0], p, z)
        close(got, want)
        jax.tree.map(close, d_got, d_want)
        light, p = model.blocks[2].operator, params["blocks"][2]["operator"]
        assert light.rope and light.qk_norm and light.slopes == lightning_slopes(4, 2, 32)
        got, d_got = _both(lambda p, z: light.apply(p, z), p, z)
        want, d_want = _both(lambda p, z: ref.lightning(p, z, CFG, 2), p, z)
        close(got, want)
        jax.tree.map(close, d_got, d_want)
        # the decay is not a no-op at this size
        plain = jax.jit(lambda p, z: ref.lightning(p, z, CFG, 2, no_decay=True))(p, z)
        assert float(jnp.max(jnp.abs(plain - want))) > 100 * TOL * float(jnp.max(jnp.abs(want)))


def test_the_two_shares_add_up_to_the_uncut_sublayers(setup):
    """Each sublayer of the uncut reference, and the outputs of its two
    tensor-parallel shares (the layer holding the first half of the heads or
    columns, and the second) added up: what the all-reduce would complete."""
    model, params, _ = setup
    z = jax.random.normal(jax.random.key(2), (2, LENGTH, 48))
    with jax.default_matmul_precision("highest"):
        # sparse attention: one key/value head and its group of two query heads a share
        p = params["blocks"][0]["operator"]
        w = p["in_proj_weight"]
        parts = [jnp.concatenate([w[32 * i:32 * i + 32], w[64 + 16 * i:80 + 16 * i], w[96 + 16 * i:112 + 16 * i]])
                 for i in (0, 1)]
        gate, out = jnp.split(p["gate_proj"]["weight"], 2), jnp.split(p["out_proj"]["weight"], 2, axis=1)
        total = 0.0
        for i in (0, 1):
            share = build(HALF if i == 0 else {**HALF, "heads_held": [2, 4], "kv_heads_held": [1, 2]}).blocks[0]
            mine = {"in_proj_weight": parts[i], "gate_proj": {"weight": gate[i]}, "out_proj": {"weight": out[i]},
                    "q_norm": p["q_norm"], "k_norm": p["k_norm"]}
            total = total + jax.jit(lambda p, z, op=share.operator: op.apply(p, z, causal=True))(mine, z)
        close(total, jax.jit(lambda p, z: ref.sparse_attention(p, z, CFG)[0])(p, z))
        # lightning: two heads a share, each keeping its decay of four
        p = params["blocks"][1]["operator"]
        q_, k_, v_ = jnp.split(p["in_proj"]["weight"], 3)
        total = 0.0
        for i in (0, 1):
            share = build({**HALF, "lightning_heads_held": [2 * i, 2 * i + 2]}).blocks[1].operator
            assert share.slopes == lightning_slopes(4, 1, 32)[2 * i:2 * i + 2]
            mine = {"in_proj": {"weight": jnp.concatenate([jnp.split(t, 2)[i] for t in (q_, k_, v_)])},
                    "gate_proj": {"weight": jnp.split(p["gate_proj"]["weight"], 2)[i]},
                    "out_proj": {"weight": jnp.split(p["out_proj"]["weight"], 2, axis=1)[i]},
                    "o_norm": {"weight": jnp.split(p["o_norm"]["weight"], 2)[i]},
                    "q_norm": p["q_norm"], "k_norm": p["k_norm"]}
            total = total + jax.jit(share.apply)(mine, z)
        close(total, jax.jit(lambda p, z: ref.lightning(p, z, CFG, 1))(p, z))
        # the SwiGLU: half of the columns a share
        p = params["blocks"][1]["ffn"]
        shares = [{"w1": {"weight": jnp.split(p["w1"]["weight"], 2)[i]}, "w3": {"weight": jnp.split(p["w3"]["weight"], 2)[i]},
                   "w2": {"weight": jnp.split(p["w2"]["weight"], 2, axis=1)[i]}} for i in (0, 1)]
        assert build().blocks[1].ffn.hidden_dim == 48
        total = sum(jax.jit(SwiGLU(48, 48).apply)(s, z) for s in shares)
        close(total, jax.jit(ref.swiglu)(p, z))
    with pytest.raises(ValueError, match="groups"):
        build({**HALF, "heads_held": [1, 3]})


# ---------------------------------------------------------------------- #
# the whole model
# ---------------------------------------------------------------------- #
def test_the_programs_tree_is_the_references(setup):
    model, params, _ = setup
    own = model.init(jax.random.key(7))
    shape_of = lambda tree: jax.tree.map(lambda a: (a.shape, a.dtype), tree)  # noqa: E731
    assert shape_of(own) == shape_of(params)
    half = build()
    assert shape_of(half.init(jax.random.key(7))) == shape_of(ref.init_params(jax.random.key(0), HALF))
    mask = model.decay_mask(own)
    assert all(bool(m) == ref.decays(path) for path, m in jax.tree_util.tree_flatten_with_path(mask)[0])
    assert [b.residual_scale for b in model.blocks] == [1.4 / 32 ** 0.5] * 4 and model.head_input_scale == 0.25
    # the arguments leave the accepted layouts as they were: no scale, no counts
    old = PatternLM(32, 32, ["conv", "full_attention"], num_heads=4, num_kv_heads=2, ffn_dim=48)
    assert old.head_input_scale is None and all(b.residual_scale is None and not b.counted for b in old.blocks)
    assert old.blocks[1].operator.sparse is None


def test_logits_loss_and_gradients_match_the_reference(setup):
    model, params, tokens = setup
    with jax.default_matmul_precision("highest"):
        logits, stats = jax.jit(model.apply)(params, tokens)
        close(logits, jax.jit(lambda p, t: ref.logits(p, t, CFG))(params, tokens))
        assert len(stats) == 1 and stats[0]["selection"].shape == (2, 2, LENGTH, 5)
        loss = lambda p: model.next_token_loss(p, tokens, train=True, block_rows=48)  # noqa: E731
        (value, counts), grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(params)
        want, facts, want_grads = jax.jit(lambda p, t: ref.loss_and_grads(p, t, CFG))(params, tokens)
        assert abs(float(value) - float(want)) < 1e-5 * float(want)
        np.testing.assert_array_equal(jnp.sort(counts[0]["selection"], -1), jnp.sort(facts[0]["own"], -1))
        assert int(counts[0]["kept_pairs"]) == sum(
            int(sa.kept_pairs(counts[0]["selection"][b, h], jnp.arange(LENGTH), 16)) for b in range(2) for h in range(2))
        got_norms, want_norms = ref.group_norms(grads), ref.group_norms(want_grads)
        assert set(want_norms) == {"embedding", "head", "norms"} | {
            f"{kind}_{i}" for kind in ("operator", "ffn") for i in range(4)}
        for name, norm in want_norms.items():
            assert abs(float(got_norms[name]) - float(norm)) <= TOL * float(norm), name
        jax.tree.map(close, grads, want_grads)


def test_three_data_parallel_adamw_steps_match_the_reference(setup):
    model, params, tokens = setup
    with jax.default_matmul_precision("highest"):
        optimizer = ht.optim.DataParallelOptimizer(ht.optim.AdamW(
            lr=ADAMW["lr"], betas=(ADAMW["b1"], ADAMW["b2"]), eps=ADAMW["eps"],
            weight_decay=ADAMW["weight_decay"], mask=model.decay_mask))
        dp = ht.nn.DataParallel(model, optimizer=optimizer)
        dp.parameters = mine = jax.tree.map(jnp.copy, params)
        step = dp.make_train_step(lambda out, t: out, forward=model.next_token_loss,
                                  stats=lambda grads, aux, *_: sum(c["kept_pairs"] for c in aux))
        state = optimizer.init_state(mine)
        theirs, adam = params, ref.adamw_init(params)
        reference_step = jax.jit(lambda p, t: ref.loss_and_grads(p, t, CFG))
        reference_update = jax.jit(lambda p, g, a: ref.adamw_step(p, g, a, **ADAMW))
        for i in range(3):
            batch = jnp.roll(tokens, i, axis=1)
            want_loss, _, grads = reference_step(theirs, batch)
            before = theirs
            theirs, adam = reference_update(theirs, grads, adam)
            moved_from = jax.tree.map(jnp.copy, mine)
            mine, state, loss, kept = step(mine, state, batch, batch)
            assert abs(float(loss) - float(want_loss)) < 2e-5 * float(want_loss) and 0 < int(kept)
            moved = ref.group_norms(jax.tree.map(jnp.subtract, mine, moved_from))
            for name, norm in ref.group_norms(jax.tree.map(jnp.subtract, theirs, before)).items():
                assert abs(float(moved[name]) - float(norm)) <= 2e-3 * float(norm) + 1e-12, (i, name)
        jax.tree.map(lambda a, b: close(a, b, 1e-4), mine, theirs)


def test_the_controls_are_told_apart(setup):
    _, params, tokens = setup
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, t: ref.logits(p, t, CFG))(params, tokens)
        for control in ({"product_dtype": jnp.bfloat16}, {"no_decay": True}, {"no_residual_scale": True}):
            other = jax.jit(lambda p, t, c=control: ref.logits(p, t, CFG, **c))(params, tokens)
            assert float(jnp.max(jnp.abs(other - want))) > 10 * TOL * float(jnp.max(jnp.abs(want))), control


def test_the_reference_works_in_blocks_without_changing_a_number(setup, monkeypatch):
    _, params, tokens = setup
    with jax.default_matmul_precision("highest"):
        whole = float(ref.loss(params, tokens, CFG)[0])
        monkeypatch.setattr(ref, "ROWS", 32)
        monkeypatch.setattr(ref, "CHUNK", 16)
        monkeypatch.setattr(ref, "HEAD_ROWS", 64)
        assert abs(float(ref.loss(params, tokens, CFG)[0]) - whole) < 1e-6 * whole


def test_the_reference_is_plain():
    with open(ref.__file__, encoding="utf-8") as fh:
        source = fh.read()
    assert "import heat_tpu" not in source and "from heat_tpu" not in source and "pallas" not in source
    assert "lax.scan(token, state, t)" in source and "argsort(-score, axis=-1, stable=True)" in source


def test_the_block_checkpoint_keeps_the_selection_and_the_forward(setup, monkeypatch):
    """A step's value and gradients run the sparse layer's selection and its
    forward kernel once (``sparse_attention.KEPT`` names their results for
    ``_remat_jit``'s policy); without the policy both run again in the
    backward."""
    import re

    model, params, tokens = setup

    def counts(m):
        text = str(jax.make_jaxpr(jax.value_and_grad(lambda p, t: m.next_token_loss(p, t, train=True)[0]))(
            params, tokens))
        return len(re.findall(r"name=_fwd_call\b", text)), len(re.findall(r"\btop_k\[", text))

    assert counts(model) == (1, 1)
    monkeypatch.setattr(jax.checkpoint_policies, "save_only_these_names", lambda *names: None)
    assert counts(build(CFG)) == (2, 2)
