"""The block checkpoint keeps what the flash kernels name: their output and
log-sum-exp (``ops.flash_attention.KEPT``, the policy of
``nn.models._remat_jit``).  So a differentiated step runs each attention
layer's forward kernel once, where a checkpoint without a policy ran it again
in the backward, and gets the same loss and gradients; a block without a flash
call keeps nothing and compiles as before."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from heat_tpu.nn.models import PatternLM
from test_ops_kernels import _fa


def build(layer_types=("global_attention", "sliding_attention")):
    return PatternLM(64, 32, list(layer_types), num_heads=4, num_kv_heads=2, window=8, ffn_dim=48)


def loss_fn(model):
    return lambda p, t: model.next_token_loss(p, t, train=True)[0]


def drop_policy(monkeypatch):
    """From here on ``_remat_jit`` builds the parent's checkpoint: no policy,
    so it keeps nothing."""
    monkeypatch.setattr(jax.checkpoint_policies, "save_only_these_names", lambda *names: None)


def forward_kernels(model):
    """``(forward kernel calls, backward kernel calls)`` in the jaxpr of a
    step's value and gradients."""
    params = jax.eval_shape(model.init, jax.random.key(0))
    tokens = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    text = str(jax.make_jaxpr(jax.value_and_grad(loss_fn(model)))(params, tokens))
    return (len(re.findall(r"name=_flash_gqa_fwd_impl\b", text)),
            len(re.findall(r"name=_flash_gqa_bwd_impl\b", text)))


def test_each_attention_layer_runs_its_forward_kernel_once():
    fa = _fa()
    before = dict(fa.path_counts)
    assert forward_kernels(build()) == (2, 2)
    # one global and one windowed layer named their residuals, once each
    assert fa.path_counts["kept"] == before["kept"] + 2
    assert fa.path_counts["pallas"] == before["pallas"] + 2 and fa.path_counts["dense"] == before["dense"]
    # a forward without gradients has no residuals to name
    model = build()
    jax.make_jaxpr(lambda p, t: model.apply(p, t))(
        jax.eval_shape(model.init, jax.random.key(0)), jax.ShapeDtypeStruct((2, 16), jnp.int32))
    assert fa.path_counts["kept"] == before["kept"] + 2


def test_without_the_policy_the_forward_runs_again(monkeypatch):
    drop_policy(monkeypatch)
    assert forward_kernels(build()) == (4, 2)


def test_loss_and_gradients_are_those_of_the_checkpoint_without_a_policy(monkeypatch):
    model = build()
    params = model.init(jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, 64)
    kept = jax.jit(jax.value_and_grad(loss_fn(model)))(params, tokens)
    drop_policy(monkeypatch)
    again = jax.jit(jax.value_and_grad(loss_fn(build())))(params, tokens)
    # the kernel saved is the kernel run again on the same operands: the same bits
    for a, b in zip(jax.tree.leaves(kept), jax.tree.leaves(again)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _lowered(model):
    params = jax.eval_shape(model.init, jax.random.key(0))
    tokens = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    text = jax.jit(jax.value_and_grad(loss_fn(model))).lower(params, tokens).as_text()
    return re.sub(r"loc\([^\n]*?\)|#loc[^\n]*", "", text)


@pytest.mark.parametrize("layer_types", [("conv",), ("conv", "sliding_attention")])
def test_a_block_without_a_flash_call_compiles_as_before(monkeypatch, layer_types):
    kept = _lowered(build(layer_types))
    drop_policy(monkeypatch)
    again = _lowered(build(layer_types))
    # the convolution's block keeps nothing either way; an attention block is another program
    assert (kept == again) == ("sliding_attention" not in layer_types)
