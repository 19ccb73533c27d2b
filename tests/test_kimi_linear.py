"""Kimi Delta Attention chunk by chunk, latent attention with keys wider than
values, a shared expert beside the routed ones and bounded expert buffers,
each against the plain reference ``kimi_linear_reference`` at a small size on
the CPU; then a whole ``PatternLM`` of ``kda`` and ``mla`` layers: logits, loss,
gradients by group, one ``DataParallel`` AdamW step, and the expert-parallel
share."""

import filecmp
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import heat_tpu as ht
import kimi_linear_reference as ref
from heat_tpu.nn.models import PatternLM
from heat_tpu.nn.moe import MoE
from heat_tpu.ops.kda import chunk_kda

HERE = os.path.dirname(os.path.abspath(__file__))
fa = sys.modules["heat_tpu.ops.flash_attention"]  # ``heat_tpu.ops.flash_attention`` is the function
kda = sys.modules["heat_tpu.ops.kda"]

CFG = {
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32, "vocab_size": 96,
    "num_attention_heads": 2, "kv_lora_rank": 24, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "linear_attn_config": {"head_dim": 16, "num_heads": 2, "short_conv_kernel_size": 4},
    "kda_gate_rank": 8, "layer_types": ["kda", "kda", "mla", "kda"], "first_k_dense_replace": 1,
    "num_experts": 8, "num_experts_per_token": 2, "experts_held": [0, 8], "num_shared_experts": 1,
    "moe_renormalize": True, "routed_scaling_factor": 2.446, "rms_norm_eps": 1e-5,
}
ADAMW = dict(lr=3e-4, b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1)
# float32 against float32 at ``highest`` precision: what is left is the order of the sums
TOL = 2e-4


def build(cfg=CFG, **kw):
    lin = cfg["linear_attn_config"]
    return PatternLM(
        cfg["vocab_size"], cfg["hidden_size"], cfg["layer_types"],
        num_heads=cfg["num_attention_heads"], ffn_dim=cfg["intermediate_size"],
        num_dense_layers=cfg["first_k_dense_replace"], num_experts=cfg["num_experts"],
        experts_per_token=cfg["num_experts_per_token"], expert_dim=cfg["moe_intermediate_size"],
        experts_held=range(*cfg["experts_held"]), routed_scaling=cfg["routed_scaling_factor"],
        conv_taps=lin["short_conv_kernel_size"], norm_eps=cfg["rms_norm_eps"], bias_std=0.1,
        tie_embedding=False, shared_expert_dim=cfg["moe_intermediate_size"],
        kda_heads=lin["num_heads"], kda_head_dim=lin["head_dim"], kda_gate_rank=cfg["kda_gate_rank"],
        kda_chunk=16, kv_rank=cfg["kv_lora_rank"], qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_shared_dim=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"], **kw)


def close(got, want, tol=TOL):
    scale = max(float(jnp.max(jnp.abs(want))), 1e-6)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol * scale, rtol=0)


@pytest.fixture(scope="module")
def setup():
    with jax.default_matmul_precision("highest"):
        model = build()
        # the reference's draw: matrices larger than at the published widths, so that
        # every operator's output is of the size of the residual stream
        params = ref.init_params(jax.random.key(0), CFG, init_std=0.2, bias_std=0.1)
        params = jax.tree_util.tree_map_with_path(
            lambda path, a: a + 0.1 * jnp.sin(jnp.arange(a.size, dtype=jnp.float32)).reshape(a.shape)
            if any(str(getattr(k, "key", "")).endswith("norm") for k in path) else a, params)
        tokens = jax.random.randint(jax.random.key(1), (3, 40), 0, CFG["vocab_size"])  # 40: a ragged last chunk
        return model, params, tokens


# ---------------------------------------------------------------------- #
# the kernel
# ---------------------------------------------------------------------- #
def _kda_inputs(length=48, heads=2, dk=16, dv=8, strong=False):
    ks = jax.random.split(jax.random.key(3), 5)
    q, k = (jax.random.normal(key, (length, heads, dk)) for key in ks[:2])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (length, heads, dv))
    if strong:  # exp(A_log) = 16 and steps of 0.1 to 1
        g = -16.0 * jax.random.uniform(ks[3], (length, heads, dk), minval=0.1, maxval=1.0)
    else:
        g = -jax.random.uniform(ks[3], (length, heads, dk), minval=1e-3, maxval=0.5)
    return q, k, v, g, jax.nn.sigmoid(jax.random.normal(ks[4], (length, heads)))


def _chunked(q, k, v, g, beta, chunk):
    heads_first = lambda t: jnp.moveaxis(t, 1, 0)  # noqa: E731
    o, state = chunk_kda(*(heads_first(t) for t in (q, k, v, g, beta)), chunk=chunk)
    return jnp.moveaxis(o, 0, 1), state


# toy shapes take the XLA form of the chunk-local part, heads of 128 the Pallas
# kernels (under the interpreter here): (chunk, length, d_k, d_v, path)
TOY_16, TOY_32, KERNEL = (16, 48, 16, 8, "dense"), (32, 48, 16, 8, "dense"), (64, 192, 128, 128, "pallas")
KERNEL_32 = (32, 96, 128, 256, "pallas")  # values wider than the keys


def _value_and_grads(fn, args):
    """``(fn(*args), its five gradients under a fixed cotangent)``, one program."""
    def scalar(*a):
        o, state = fn(*a)
        w_o, w_s = jax.random.normal(jax.random.key(4), o.shape), jax.random.normal(jax.random.key(5), state.shape)
        return jnp.sum(o * w_o) + jnp.sum(state * w_s), (o, state)

    (_, out), grads = jax.jit(jax.value_and_grad(scalar, argnums=tuple(range(5)), has_aux=True))(*args)
    return out, grads


@pytest.mark.parametrize("strong", [False, True], ids=["mild_decay", "strong_decay"])
@pytest.mark.parametrize("chunk, length, dk, dv, path", [TOY_16, TOY_32, KERNEL, KERNEL_32],
                         ids=["16", "32", "kernel_64", "kernel_32"])
def test_chunk_kda_is_the_token_recurrence(chunk, length, dk, dv, path, strong):
    """Output, final state and all five gradients for a sequence of several
    chunks.  With the strong decay ``-G`` passes 88 inside a chunk, where a
    form that exponentiates ``-G`` alone has left float32."""
    with jax.default_matmul_precision("highest"):
        args = _kda_inputs(length, dk=dk, dv=dv, strong=strong)
        if strong:
            in_chunk = -jnp.cumsum(args[3][:chunk], axis=0)
            assert float(in_chunk.max()) > 88.8 and not np.isfinite(np.exp(np.float32(in_chunk.max())))
        before = dict(kda.path_counts)
        want, d_want = _value_and_grads(ref.delta_rule, args)
        got, d_got = _value_and_grads(lambda *a: _chunked(*a, chunk), args)
        assert {n: kda.path_counts[n] - before[n] for n in before} == {"pallas": 0, "dense": 0, path: 1}
        close(got[0], want[0], 1e-5)
        close(got[1], want[1], 1e-5)
        for name, a, b in zip("qkvgb", d_got, d_want):
            assert np.all(np.isfinite(a))
            # d g at heads of 128 under the strong decay: the float32 token recurrence lies 2e-5
            # (these kernels) to 5e-5 (the XLA form at the same shapes) from either
            close(a, b, 5e-5 if (name, path, strong) == ("g", "pallas", True) else 1e-5)


@pytest.mark.parametrize("strong", [False, True], ids=["mild_decay", "strong_decay"])
def test_chunk_kda_kernels_are_the_xla_form(strong):
    """The same inputs through both executors of the chunk-local part, a batch
    axis before the heads: outputs and the five gradients."""
    with jax.default_matmul_precision("highest"):
        args = tuple(jnp.stack([jnp.moveaxis(t, 1, 0), jnp.moveaxis(t[::-1], 1, 0)])
                     for t in _kda_inputs(128, dk=128, dv=128, strong=strong))
        assert kda._pallas_gate(args[0], args[2], 64) == 2
        (want, d_want), (got, d_got) = (_value_and_grads(lambda *a, tile=tile: kda._chunk_kda(*a, 64, tile), args)
                                        for tile in (0, 2))
        for name, a, b in zip("osqkvgb", (*got, *d_got), (*want, *d_want)):
            assert a.shape == b.shape and a.dtype == b.dtype
            close(a, b, 1e-4 if (name, strong) == ("g", True) else 1e-5)  # see the test above


@pytest.mark.parametrize("chunk, length, dk, dv, path", [(16, 40, 16, 8, "dense"), (64, 130, 128, 128, "pallas")],
                         ids=["16", "kernel_64"])
def test_chunk_kda_pads_a_ragged_length_and_batches_leading_axes(chunk, length, dk, dv, path):
    """40 tokens are two and a half chunks of 16; 130 are three chunks of 64,
    which the kernels take as one tile."""
    with jax.default_matmul_precision("highest"):
        q, k, v, g, beta = _kda_inputs(length, dk=dk, dv=dv)
        want_o, want_s = jax.jit(ref.delta_rule)(q, k, v, g, beta)
        two = lambda t: jnp.stack([jnp.moveaxis(t, 1, 0)] * 2)  # noqa: E731  (batch, heads, S, ...)
        before = kda.path_counts[path]
        o, state = chunk_kda(two(q), two(k), two(v), two(g), two(beta), chunk=chunk)
        assert kda.path_counts[path] == before + 1
        assert o.shape == (2, 2, length, dv) and state.shape == (2, 2, dk, dv) and state.dtype == jnp.float32
        close(jnp.moveaxis(o[1], 0, 1), want_o, 1e-5)
        close(state[0], want_s, 1e-5)


@pytest.mark.parametrize("chunk, length, dk, dv, path", [TOY_16, KERNEL], ids=["16", "kernel_64"])
def test_chunk_kda_in_bfloat16_stays_near(chunk, length, dk, dv, path):
    q, k, v, g, beta = _kda_inputs(length, dk=dk, dv=dv)
    want, _ = jax.jit(ref.delta_rule)(q, k, v, g, beta)
    before = kda.path_counts[path]
    got, _ = _chunked(*(t.astype(jnp.bfloat16) for t in (q, k, v)), g, beta, chunk)
    assert got.dtype == jnp.bfloat16 and kda.path_counts[path] == before + 1
    close(got.astype(jnp.float32), want, 3e-2)


def test_chunk_kda_takes_the_kernels_by_platform_and_shapes(monkeypatch):
    """The gate reads the platform of the data and the shapes and nothing
    else: the chunks a grid step, or 0 for the XLA form."""
    probe = lambda length, dk, dv: (jax.ShapeDtypeStruct((2, 4, length, dk), jnp.bfloat16),  # noqa: E731
                                    jax.ShapeDtypeStruct((2, 4, length, dv), jnp.bfloat16))
    assert kda._pallas_gate(*probe(128, 128, 128), 64) == 2
    assert kda._pallas_gate(*probe(500, 128, 128), 64) == 8  # padded to a tile of eight chunks
    assert kda._pallas_gate(*probe(500, 256, 128), 64) == 4  # half as many of heads twice as wide
    assert kda._pallas_gate(*probe(8192, 128, 128), 64) == 0  # the interpreter, at test scale only
    assert kda._pallas_gate(*probe(500, 128, 256), 128) == 2 and kda._backward_tile(2, 128, 256) == 1
    for dk, dv, chunk in [(16, 8, 16), (128, 64, 64), (64, 128, 64), (128, 128, 8), (128, 128, 48), (128, 128, 256)]:
        assert kda._pallas_gate(*probe(128, dk, dv), chunk) == 0
    monkeypatch.setattr(kda, "platform_of", lambda q: "tpu")
    monkeypatch.setattr(kda, "_kernel_mesh", lambda q: None)
    assert kda._pallas_gate(*probe(8192, 128, 128), 64) == 8
    monkeypatch.setattr(kda, "platform_of", lambda q: "gpu")
    assert kda._pallas_gate(*probe(128, 128, 128), 64) == 0
    q, k, v, g, beta = (jnp.moveaxis(t, 1, 0) for t in _kda_inputs(128, dk=128, dv=128))
    before = dict(kda.path_counts)
    chunk_kda(q, k, v, g, beta, chunk=64)
    assert kda.path_counts == {**before, "dense": before["dense"] + 1}


# ---------------------------------------------------------------------- #
# flash attention with values of another width than the keys
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("d, dv, length, hq, hk", [(192, 128, 256, 2, 2), (48, 16, 200, 4, 4), (32, 64, 384, 4, 2)],
                         ids=["192_128", "48_16_ragged", "32_64_gqa"])
def test_flash_attention_takes_values_of_another_width(d, dv, length, hq, hk):
    ks = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(ks[0], (2, hq, length, d))
    k = jax.random.normal(ks[1], (2, hk, length, d))
    v = jax.random.normal(ks[2], (2, hk, length, dv))
    w = jax.random.normal(ks[3], (2, hq, length, dv))
    g = hq // hk
    dense = lambda q, k, v: fa._dense_attention(  # noqa: E731
        q, jnp.repeat(k, g, 1), jnp.repeat(v, g, 1), True, d ** -0.5, length)
    flash = lambda q, k, v: fa.flash_attention_gqa(q, k, v, causal=True)  # noqa: E731
    before = fa.path_counts["pallas"]
    both = lambda fn: jax.jit(jax.value_and_grad(  # noqa: E731
        lambda *a: (lambda out: (jnp.sum(out * w), out))(fn(*a)), (0, 1, 2), has_aux=True))(q, k, v)
    ((_, got), d_got), ((_, want), d_want) = both(flash), both(dense)
    assert fa.path_counts["pallas"] == before + 1 and got.shape == (2, hq, length, dv)
    close(got, want, 1e-5)
    for a, b in zip(d_got, d_want):
        assert a.shape == b.shape
        close(a, b, 1e-5)


def test_flash_attention_of_equal_widths_is_what_it_was():
    """Equal widths take the blocks, the gate and the kernels they took: the
    value's width only enters where it differs."""
    q = jax.random.normal(jax.random.key(1), (2, 2, 256, 64), jnp.bfloat16)
    for length, d in [(256, 64), (8192, 64), (8192, 128), (4096, 256), (1000, 48)]:
        probe = jax.ShapeDtypeStruct((1, length, d), jnp.bfloat16)
        assert fa._pallas_gate(probe, length, d) == fa._pallas_gate(probe, length, d, d)
    assert fa._pallas_gate(jax.ShapeDtypeStruct((1, 8192, 192), jnp.bfloat16), 8192, 192, 128)[1] == 1024
    k, v = q[::-1], q * 0.5
    got = fa.flash_attention(q, k, v, causal=True)
    flat = lambda t: t.reshape(4, 256, 64)  # noqa: E731
    core = fa._flash(flat(q), flat(k), flat(v), True, 0.125, 256, True)
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)),
                                  np.asarray(core.reshape(q.shape).astype(jnp.float32)))
    with pytest.raises(ValueError, match="leading axes"):
        fa.flash_attention(q, k, v[:, :1])


# ---------------------------------------------------------------------- #
# the layers
# ---------------------------------------------------------------------- #
def _both(fn, p, z):
    """``(fn(p, z), its gradients by p and z under a fixed cotangent)``, one program."""
    def scalar(p, z):
        out = fn(p, z)
        out = out[0] if isinstance(out, tuple) else out
        return jnp.sum(jnp.sin(out)), out

    (_, out), grads = jax.jit(jax.value_and_grad(scalar, (0, 1), has_aux=True, allow_int=True))(p, z)
    return out, grads


def test_kimi_delta_attention_matches_the_reference(setup):
    model, params, _ = setup
    with jax.default_matmul_precision("highest"):
        z = jax.random.normal(jax.random.key(2), (2, 40, 64))
        p = params["blocks"][1]["operator"]
        layer = model.blocks[1].operator
        assert isinstance(layer, ht.nn.KimiDeltaAttention)
        (got, d_got), (want, d_want) = _both(layer.apply, p, z), _both(lambda p, z: ref.kda(p, z, CFG), p, z)
        close(got, want)
        jax.tree.map(close, d_got, d_want)
        assert float(jnp.abs(d_want[0]["A_log"]).max()) > 0 and float(jnp.abs(d_want[0]["dt_bias"]).max()) > 0


def test_kimi_delta_attention_with_heads_of_a_lane_tile_matches_the_reference():
    """Heads of 128: the convolution, SiLU and norms by ``conv_silu_heads``'
    kernels and the chunks' parts by ``chunk_kda``'s (both under the
    interpreter here), the reference the same token recurrence."""
    conv = sys.modules["heat_tpu.ops.short_conv"]
    cfg = {**CFG, "linear_attn_config": {"head_dim": 128, "num_heads": 2, "short_conv_kernel_size": 4}}
    with jax.default_matmul_precision("highest"):
        z = jax.random.normal(jax.random.key(2), (2, 40, 64))
        p = ref.init_params(jax.random.key(0), cfg, init_std=0.2)["blocks"][1]["operator"]
        layer = ht.nn.KimiDeltaAttention(64, 2, 128, conv_taps=4, gate_rank=cfg["kda_gate_rank"], chunk=16,
                                         eps=cfg["rms_norm_eps"])
        assert jax.tree.map(jnp.shape, layer.init(jax.random.key(0))) == jax.tree.map(jnp.shape, p)
        before = dict(conv.path_counts), dict(kda.path_counts)
        got, d_got = _both(layer.apply, p, z)
        assert conv.path_counts == {**before[0], "pallas": before[0]["pallas"] + 1}
        assert kda.path_counts == {**before[1], "pallas": before[1]["pallas"] + 1}
        want, d_want = _both(lambda p, z: ref.kda(p, z, cfg), p, z)
        close(got, want)
        jax.tree.map(close, d_got, d_want)
        assert float(jnp.abs(d_want[0]["conv"]["weight"]).max()) > 0


def test_latent_attention_matches_the_reference(setup):
    model, params, _ = setup
    with jax.default_matmul_precision("highest"):
        z = jax.random.normal(jax.random.key(2), (2, 40, 64))
        p = params["blocks"][2]["operator"]
        layer = model.blocks[2].operator
        assert isinstance(layer, ht.nn.LatentAttention)
        before = fa.path_counts["pallas"]
        (got, d_got), (want, d_want) = _both(layer.apply, p, z), _both(lambda p, z: ref.mla(p, z, CFG), p, z)
        assert fa.path_counts["pallas"] == before + 1  # 24-wide keys, 16-wide values, in the kernel
        close(got, want)
        jax.tree.map(close, d_got, d_want)
        # causal: a later token changes no earlier output
        later = jax.jit(layer.apply)(p, z.at[:, 25:].set(0.0))
        np.testing.assert_allclose(later[:, :25], got[:, :25], atol=1e-5)


def test_expert_layer_with_a_shared_expert_matches_the_reference(setup):
    model, params, _ = setup
    with jax.default_matmul_precision("highest"):
        z = jax.random.normal(jax.random.key(2), (2, 40, 64))
        p = params["blocks"][1]["ffn"]
        layer = model.blocks[1].ffn
        _, stats = jax.jit(layer.apply_with_stats)(p, z)
        _, rows = jax.jit(lambda p, z: ref.experts(p, z, CFG))(p, z)
        np.testing.assert_array_equal(stats["rows"], rows)
        assert int(stats["dropped"]) == 0 and int(rows.sum()) == 2 * 40 * 2
        (got, d_got), (want, d_want) = _both(layer.apply_with_stats, p, z), _both(lambda p, z: ref.experts(p, z, CFG), p, z)
        close(got, want)
        jax.tree.map(close, d_got, d_want)
        without, _ = jax.jit(lambda p, z: ref.experts(p, z, CFG, shared=False))(p, z)
        close(got - without, ref.dense_ffn(p["shared"], z))


def test_shares_add_up_to_the_uncut_layer(setup):
    """The parts that all ranks' ``experts_held`` give, the shared expert
    counted once, add up to the uncut reference's expert layer."""
    _, params, _ = setup
    with jax.default_matmul_precision("highest"):
        z = jax.random.normal(jax.random.key(2), (2, 40, 64))
        p = params["blocks"][1]["ffn"]
        whole, rows = jax.jit(lambda p, z: ref.experts(p, z, CFG))(p, z)
        shared = ref.dense_ffn(p["shared"], z)
        total, counted = jnp.zeros_like(whole), []
        for lo in range(0, 8, 2):  # four ranks of two experts
            rank = MoE(64, 8, hidden_dim=32, top_k=2, gated=True, scoring="sigmoid", expert_bias=True,
                       routed_scaling=2.446, dispatch="sorted", experts_held=range(lo, lo + 2), shared_dim=32)
            mine = {**p, **{n: p[n][lo:lo + 2] for n in ("w1", "w2", "w3")}}
            part, stats = jax.jit(rank.apply_with_stats)(mine, z)
            cut, _ = jax.jit(lambda p, z, lo=lo: ref.experts(p, z, {**CFG, "experts_held": [lo, lo + 2]}))(mine, z)
            close(part, cut)
            total = total + part - shared  # every rank computes the shared expert alike
            counted.append(stats["rows"])
        close(total + shared, whole)
        np.testing.assert_array_equal(jnp.concatenate(counted), rows)


def _bounded(bound, held=range(0, 8)):
    return MoE(64, 8, hidden_dim=32, top_k=2, gated=True, scoring="sigmoid", expert_bias=True,
               routed_scaling=2.446, dispatch="sorted", experts_held=held, shared_dim=32, rows_bound=bound)


def test_rows_bound_not_reached_is_the_unbounded_path(setup):
    model, params, _ = setup
    with jax.default_matmul_precision("highest"):
        z = jax.random.normal(jax.random.key(2), (2, 40, 64))
        p = params["blocks"][1]["ffn"]
        want, want_stats = jax.jit(model.blocks[1].ffn.apply_with_stats)(p, z)
        for bound in (160, 200):  # exactly the 160 token-slots, and more
            got, stats = jax.jit(_bounded(bound).apply_with_stats)(p, z)
            close(got, want, 1e-6)
            np.testing.assert_array_equal(stats["rows"], want_stats["rows"])
            assert int(stats["dropped"]) == 0
        f = lambda layer: _both(layer.apply_with_stats, p, z)[1]  # noqa: E731
        jax.tree.map(lambda a, b: close(a, b, 1e-5), f(_bounded(160)), f(model.blocks[1].ffn))
        # a rank that holds a quarter of the experts needs a quarter of the rows
        held = range(2, 4)
        mine = {**p, **{n: p[n][2:4] for n in ("w1", "w2", "w3")}}
        want, want_stats = MoE(64, 8, hidden_dim=32, top_k=2, gated=True, scoring="sigmoid", expert_bias=True,
                               routed_scaling=2.446, dispatch="sorted", experts_held=held,
                               shared_dim=32).apply_with_stats(mine, z)
        got, stats = jax.jit(_bounded(int(want_stats["rows"].sum()), held).apply_with_stats)(mine, z)
        close(got, want, 1e-6)
        assert int(stats["dropped"]) == 0


def test_rows_bound_reached_drops_the_rows_past_it_and_counts_them(setup):
    _, params, _ = setup
    with jax.default_matmul_precision("highest"):
        z = jax.random.normal(jax.random.key(2), (2, 40, 64))
        p = params["blocks"][1]["ffn"]
        full, full_stats = jax.jit(_bounded(160).apply_with_stats)(p, z)
        rows = np.asarray(full_stats["rows"])
        bound = int(rows[:5].sum()) + 3  # experts 0 to 4 whole, three rows of expert 5
        got, stats = jax.jit(_bounded(bound).apply_with_stats)(p, z)
        np.testing.assert_array_equal(stats["rows"], rows)  # what was routed, as before
        assert int(stats["dropped"]) == 160 - bound
        # nothing else changes: the tokens of the dropped slots lose those experts' parts, the rest are the same
        _, idx = _bounded(160)._route(p, z.reshape(-1, 64))
        order = np.argsort(np.asarray(idx).reshape(-1), kind="stable")
        touched = np.zeros(80, bool)
        touched[order[bound:] // 2] = True
        diff = np.abs(np.asarray(got - full)).reshape(80, 64).max(axis=-1)
        assert np.all(diff[~touched] < 1e-6) and np.all(diff[touched] > 1e-6)
        with pytest.raises(ValueError, match="rows_bound"):
            MoE(64, 8, top_k=2, rows_bound=16)


# ---------------------------------------------------------------------- #
# the whole model
# ---------------------------------------------------------------------- #
_reference_step = jax.jit(lambda params, tokens: ref.loss_and_grads(params, tokens, CFG))


def test_the_programs_tree_is_the_references(setup):
    model, params, _ = setup
    own = model.init(jax.random.key(7))
    shape_of = lambda tree: jax.tree.map(lambda a: (a.shape, a.dtype), tree)  # noqa: E731
    assert shape_of(own) == shape_of(params)
    a_log, dt_bias = (own["blocks"][0]["operator"][n] for n in ("A_log", "dt_bias"))
    assert 0.0 <= float(a_log.min()) and float(a_log.max()) <= np.log(16.0)
    dt = jax.nn.softplus(dt_bias)
    assert 1e-3 * 0.99 <= float(dt.min()) and float(dt.max()) <= 1e-1 * 1.01
    assert not np.array_equal(own["blocks"][0]["operator"]["A_log"], own["blocks"][1]["operator"]["A_log"])
    mask = model.decay_mask(own)
    flat = jax.tree_util.tree_flatten_with_path(mask)[0]
    assert all(bool(m) == ref.decays(path) for path, m in flat)
    assert mask["head"]["weight"] and not mask["embed"]["weight"]
    assert not mask["blocks"][0]["operator"]["A_log"] and not mask["blocks"][0]["operator"]["dt_bias"]
    with pytest.raises(ValueError, match="'kda' and 'mla'"):
        PatternLM(8, 8, ["mamba"], num_heads=1, ffn_dim=8)


def test_the_vectors_of_the_decay_stay_float32_under_bfloat16(setup):
    model, params, _ = setup
    cast = build(dtype=jnp.bfloat16)._cast(params["blocks"][0])
    assert cast["operator"]["A_log"].dtype == cast["operator"]["dt_bias"].dtype == jnp.float32
    assert cast["operator"]["in_proj"]["weight"].dtype == jnp.bfloat16
    assert cast["operator"]["o_norm"]["weight"].dtype == jnp.float32


def test_logits_loss_and_gradients_match_the_reference(setup):
    model, params, tokens = setup
    with jax.default_matmul_precision("highest"):
        logits, stats = jax.jit(model.apply)(params, tokens)
        close(logits, jax.jit(lambda p, t: ref.logits(p, t, CFG))(params, tokens))
        assert len(stats) == 3 and all(int(s["dropped"]) == 0 for s in stats)

        def loss(p):
            out, routing = model.apply(p, tokens, train=True)
            return ht.nn.losses.next_token_cross_entropy(out, tokens), routing

        (value, routing), grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(params)
        want, rows, want_grads = _reference_step(params, tokens)
        assert abs(float(value) - float(want)) < 1e-5 * float(want)
        for mine, theirs in zip(routing, rows):
            np.testing.assert_array_equal(mine["rows"], theirs)
        got_norms, want_norms = ref.group_norms(grads), ref.group_norms(want_grads)
        assert set(want_norms) == {"embedding", "head", "norms", "router", "experts", "shared_expert", "dense_ffn",
                                   "selection_bias", "operator_0", "operator_1", "operator_2", "operator_3"}
        for name, norm in want_norms.items():
            assert abs(float(got_norms[name]) - float(norm)) <= TOL * float(norm), name
        jax.tree.map(close, grads, want_grads)


def test_one_data_parallel_adamw_step_matches_the_reference(setup):
    model, params, tokens = setup
    with jax.default_matmul_precision("highest"):
        optimizer = ht.optim.DataParallelOptimizer(ht.optim.AdamW(
            lr=ADAMW["lr"], betas=(ADAMW["b1"], ADAMW["b2"]), eps=ADAMW["eps"],
            weight_decay=ADAMW["weight_decay"], mask=model.decay_mask))
        dp = ht.nn.DataParallel(model, optimizer=optimizer)
        dp.parameters = start = jax.tree.map(jnp.copy, params)
        step = dp.make_train_step(
            lambda out, t: (ht.nn.losses.next_token_cross_entropy(out[0], t), out[1]),
            stats=lambda grads, aux, *_: sum(r["dropped"] for r in aux))
        new, _, loss, dropped = step(start, optimizer.init_state(start), tokens, tokens)
        want_loss, _, grads = _reference_step(params, tokens)
        want, _ = jax.jit(lambda p, g: ref.adamw_step(p, g, ref.adamw_init(p), **ADAMW))(params, grads)
        assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss) and int(dropped) == 0
        moved = ref.group_norms(jax.tree.map(jnp.subtract, new, params))
        for name, norm in ref.group_norms(jax.tree.map(jnp.subtract, want, params)).items():
            assert abs(float(moved[name]) - float(norm)) <= 1e-3 * float(norm) + 1e-12, name
        assert float(moved["selection_bias"]) == 0.0


def test_a_lower_precision_control_is_told_apart(setup):
    _, params, tokens = setup
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, t: ref.logits(p, t, CFG))(params, tokens)
        low = jax.jit(lambda p, t: ref.logits(p, t, CFG, product_dtype=jnp.bfloat16))(params, tokens)
        assert float(jnp.max(jnp.abs(low - want))) > 10 * TOL * float(jnp.max(jnp.abs(want)))


def test_the_reference_is_plain_and_the_benchmarks_copy_is_this_file():
    other = os.path.join(os.path.dirname(HERE), "chipbench", "references", "kimi_linear.py")
    assert filecmp.cmp(os.path.join(HERE, "kimi_linear_reference.py"), other, shallow=False)
    with open(other, encoding="utf-8") as fh:
        source = fh.read()
    assert "import heat_tpu" not in source and "from heat_tpu" not in source and "pallas" not in source
    assert "lax.scan(token" in source  # the recurrence token by token, not the chunk form
