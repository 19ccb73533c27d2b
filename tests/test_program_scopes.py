"""The names a program gives its layers (``jax.named_scope("ht.<layer>")``), read
back from what the compiler keeps of them: a toy ``PatternLM`` training step of
each layer pattern through ``DataParallel.make_train_step``, compiled on the CPU,
and both k-means fit programs.  The benchmark's per-layer metrics read
device time by these names (``chipbench/harness/scopes.py``), so a scope that a
refactor drops fails here, not on a traced line on the chip."""

import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest

import heat_tpu as ht
from heat_tpu.cluster.kmeans import KMeans
from heat_tpu.nn.models import PatternLM
from heat_tpu.ops.sparse_attention import SparseConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from chipbench.harness import scopes  # noqa: E402
from chipbench.harness import trace as tr  # noqa: E402

EXPERTS = dict(num_experts=8, experts_held=range(0, 4), bias_std=0.1, dtype=jnp.bfloat16)
# pattern -> (the model, the layers its step must name beside those of every pattern)
PATTERNS = {
    "conv_attention_experts": (
        lambda: PatternLM(96, 64, ["conv", "full_attention", "conv"], num_heads=4, num_kv_heads=2,
                          ffn_dim=96, num_dense_layers=1, experts_per_token=2, expert_dim=48, **EXPERTS),
        {"ht.shortconv.proj", "ht.shortconv", "ht.attention.proj", "ht.attention", "ht.mlp"}),
    "kda_latent_attention_shared_expert": (
        lambda: PatternLM(96, 64, ["kda", "mla", "kda"], num_heads=2, ffn_dim=96, num_dense_layers=1,
                          experts_per_token=2, expert_dim=32, conv_taps=4, tie_embedding=False,
                          shared_expert_dim=32, expert_rows_bound=64, kda_heads=2, kda_head_dim=16,
                          kda_gate_rank=8, kda_chunk=16, kv_rank=24, qk_nope_dim=16, qk_shared_dim=8,
                          v_dim=16, **EXPERTS),
        {"ht.kda.proj", "ht.kda.conv", "ht.kda.gate", "ht.kda", "ht.kda.prepare", "ht.kda.recur",
         "ht.attention.proj", "ht.attention", "ht.mlp", "ht.moe.shared"}),
    "global_sliding_route_before_operator": (
        lambda: PatternLM(96, 48, ["global_attention", "sliding_attention"], num_heads=4, num_kv_heads=2,
                          head_dim=16, qk_norm=False, window=8, rope_kinds=("sliding_attention",),
                          ffn_dim=None, num_dense_layers=0, experts_per_token=3, expert_dim=24,
                          router_scoring="softmax", expert_activation="relu", route_before_operator=True,
                          tie_embedding=False, **{**EXPERTS, "bias_std": 0.0}),
        {"ht.attention.proj", "ht.attention", "ht.attention.window"}),
    "rotated_latent_attention_shared_experts": (
        lambda: PatternLM(96, 64, ["mla", "mla"], num_heads=2, ffn_dim=96, num_dense_layers=1,
                          experts_per_token=2, expert_dim=32, tie_embedding=False, shared_expert_dim=64,
                          expert_rows_bound=64, kv_rank=24, qk_nope_dim=16, qk_shared_dim=8, v_dim=16,
                          rope_kinds=("mla",), rope_base=5e4, kv_norm_eps=1e-6, **EXPERTS),
        {"ht.attention.proj", "ht.attention.rope", "ht.attention", "ht.mlp", "ht.moe.shared"}),
    "sparse_lightning_held_shares_scaled": (
        lambda: PatternLM(96, 64, ["sparse_attention", "lightning", "lightning"], num_heads=4, num_kv_heads=2,
                          head_dim=16, ffn_dim=96, num_dense_layers=2, experts_per_token=2, expert_dim=48,
                          rope_kinds=("lightning",), attention_gate=True, tie_embedding=False,
                          sparse=SparseConfig(kernel_size=4, kernel_stride=2, block_size=8, window_size=8,
                                              topk=2, dense_len=16),
                          lightning_heads=4, lightning_head_dim=16, lightning_chunk=16, heads_held=range(2, 4),
                          kv_heads_held=range(1, 2), lightning_heads_held=range(2, 4), ffn_held=range(0, 48),
                          residual_scale=0.25, head_input_scale=0.5, embedding_scale=12.0, **EXPERTS),
        {"ht.attention.proj", "ht.attention", "ht.attention.gate", "ht.sparse_attention.select",
         "ht.sparse_attention", "ht.lightning", "ht.lightning.gate", "ht.mlp"}),
}
EVERY_PATTERN = {"ht.lm.cast", "ht.lm.embed", "ht.lm.block", "ht.lm.norm", "ht.lm.head_loss",
                 "ht.moe.route", "ht.moe.dispatch", "ht.moe.experts", "ht.moe.combine", "ht.optim.update"}
# What a step may run under no ``ht.`` scope: the caller's ``stats=`` hook, which is the
# caller's to name (the benchmark's jobs sum their gradient norms there, as ``_stats`` below)
ALLOWED = re.compile(r"^jit\(step\)/(jit\(main\)/)?(mul|reduce_sum|add|sqrt|convert_element_type)$")


def _loss(out, tokens):
    logits, routing = out
    return ht.nn.losses.next_token_cross_entropy(logits, tokens), routing


def _stats(grads, routing, *update):
    return jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads))), routing


def _op_names(text: str) -> list:
    """The ``op_name`` of every instruction that the program, not the compiler,
    made (a reducer's or a parameter's has no ``jit(...)`` at its head)."""
    return [op for op in re.findall(r'op_name="([^"]*)"', text) if op.startswith("jit(")]


@pytest.fixture(scope="module", params=sorted(PATTERNS))
def compiled_step(request):
    build, layers = PATTERNS[request.param]
    model = build()
    optimizer = ht.optim.DataParallelOptimizer(ht.optim.AdamW(lr=3e-4, weight_decay=0.1, mask=model.decay_mask))
    dp = ht.nn.DataParallel(model, optimizer=optimizer)
    params = jax.eval_shape(model.init, jax.random.key(0))
    # not ``init_state``: it keeps what it returns on the optimizer, here a tracer
    state = jax.eval_shape(optimizer.optax_optimizer.init, params)
    tokens = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    step = dp.make_train_step(_loss, stats=_stats)
    return _op_names(step.lower(params, state, tokens, tokens).compile().as_text()), layers


def test_every_instruction_of_a_step_is_under_a_layers_name(compiled_step):
    names, layers = compiled_step
    assert len(names) > 500
    unnamed = sorted({op for op in names if not scopes.layers(tr.scope_of(op)) and not ALLOWED.match(op)})
    assert not unnamed, unnamed
    assert {layer for op in names for layer in scopes.layers(tr.scope_of(op))} == EVERY_PATTERN | layers


# jax wraps the outermost component of the name stack at each transformation, so the scope
# opened in the differentiated function itself is never bare, the two opened inside the
# block's jitted call always are, and the embedding's and head's cast (outside) is wrapped
FORMS = {"ht.lm.block": {"jvp(ht.lm.block)", "transpose(jvp(ht.lm.block))"},
         "ht.lm.norm": {"ht.lm.norm"},
         "ht.lm.cast": {"ht.lm.cast", "jvp(ht.lm.cast)"}}


@pytest.mark.parametrize("name", sorted(FORMS))
def test_the_models_own_scopes_forward_backward_and_recomputed(compiled_step, name):
    names, _ = compiled_step
    found = [tr.scope_of(op).split("/") for op in names if name in scopes.layers(tr.scope_of(op))]
    assert {part for parts in found for part in parts if scopes.bare(part) == name} == FORMS[name]
    backward = [parts for parts in found if parts[0].startswith("transpose(")]
    assert backward and len(backward) < len(found)
    # what jax.checkpoint runs again, which ``recompute_ms`` reads
    assert any("rematted_computation" in parts for parts in backward)
    # and as the innermost name it holds something of its own
    assert any(scopes.layers("/".join(parts))[-1] == name for parts in found)


def _fit_scopes(which: str) -> list:
    """The scopes of the compiled fit program's instructions, a ``shard_map``
    at their head taken off."""
    x = jax.ShapeDtypeStruct((64, 4), jnp.float32)
    centers = jax.ShapeDtypeStruct((3, 4), jnp.float32)
    if which == "one_program":
        lowered = KMeans._fit_program().lower(x, centers, 5, jnp.float32(0.0))
    else:
        lowered = KMeans._fit_program_sharded(ht.get_comm()).lower(
            x, centers, jnp.int32(64), jnp.int32(5), jnp.float32(0.0))
    return [(tr.scope_of(op).removeprefix("shard_map/"), op.rsplit("/", 1)[-1])
            for op in _op_names(lowered.compile().as_text())]


@pytest.mark.parametrize("which", ["one_program", "sharded"])
def test_the_fit_names_its_two_layers(which):
    """``ht.kmeans.em`` inside the ``while`` body (the sharded program's two
    ``psum``s with it), ``ht.kmeans.assign`` after the loop."""
    found = _fit_scopes(which)
    in_loop = {scope for scope, _ in found if scope.startswith("while/")}
    after = {scope for scope, _ in found} - in_loop
    assert any(s.startswith("while/body/ht.kmeans.em") for s in in_loop)
    assert any(s.split("/")[0] == "ht.kmeans.assign" for s in after)
    assert not any("ht.kmeans.assign" in s for s in in_loop) and not any("ht.kmeans.em" in s for s in after)
    sums = [scope for scope, primitive in found if primitive == "psum"]
    assert (len(sums) >= 3 and sum(s.startswith("while/body/ht.kmeans.em") for s in sums) >= 2) == (which == "sharded")
