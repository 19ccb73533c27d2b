"""Linear-attention sequence operators: ``KimiDeltaAttention``, the gated
delta rule with a decay a channel of Kimi Linear (arXiv:2510.26692), around
:func:`heat_tpu.ops.kda.chunk_kda`."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .modules import Linear, Module, rms_normalize

__all__ = ["KimiDeltaAttention", "LightningAttention", "lightning_slopes"]


class KimiDeltaAttention(Module):
    """Kimi Delta Attention over ``num_heads`` heads of ``head_dim``
    (``P = num_heads * head_dim``), causal by construction:

    - ``q, k, v = SiLU(conv(x W_in))``: one input projection to ``3 P``, then
      a causal depthwise convolution of ``conv_taps`` positions a channel;
      ``q`` and ``k`` are L2-normalised a head (eps 1e-6) and ``q`` scaled by
      ``head_dim ** -0.5``;
    - log-decay a channel ``g = -exp(A_log) softplus(x W_fa W_fb + dt_bias)``
      (``A_log`` one a head, the projection through ``gate_rank``) and write
      strength ``beta = sigmoid(x W_b)`` a head;
    - the gated delta rule (``chunk_kda``, ``chunk`` tokens at a time);
    - ``out = (RMSNorm_head(o) * sigmoid(x W_ga W_gb)) W_o``, the norm's one
      weight vector shared by the heads.

    No bias but ``dt_bias``.  Scopes: ``ht.kda.proj`` (the projections of the
    hidden state), ``ht.kda.conv`` (convolution, SiLU, L2 norms: one operator,
    :func:`heat_tpu.ops.short_conv.conv_silu_heads`, a fused pass a direction
    where its kernels run), ``ht.kda.gate`` (decay, beta, output norm and
    gate), ``ht.kda`` (the kernel).  Convolution, norms and gates are float32
    whatever ``x``'s dtype; matrices are brought to ``x``'s dtype where they
    are used.
    """

    def __init__(self, embed_dim: int, num_heads: int, head_dim: int, *, conv_taps: int = 4,
                 gate_rank: int = None, chunk: int = 64, eps: float = 1e-5):
        self.embed_dim, self.num_heads, self.head_dim = embed_dim, num_heads, head_dim
        self.conv_taps, self.chunk, self.eps = conv_taps, chunk, eps
        self.gate_rank = head_dim if gate_rank is None else gate_rank

    def init(self, key):
        e, h, r = self.embed_dim, self.num_heads, self.gate_rank
        p = h * self.head_dim
        shapes = {"in_proj": (e, 3 * p), "f_a": (e, r), "f_b": (r, p), "g_a": (e, r), "g_b": (r, p),
                  "b_proj": (e, h), "out_proj": (p, e)}
        keys = jax.random.split(key, len(shapes) + 2)
        out = {name: Linear(*shape, bias=False).init(k) for (name, shape), k in zip(shapes.items(), keys)}
        bound = self.conv_taps ** -0.5
        out["conv"] = {"weight": jax.random.uniform(keys[-2], (3 * p, self.conv_taps), minval=-bound, maxval=bound)}
        out.update(self.init_decay(keys[-1]))
        out["o_norm"] = {"weight": jnp.ones((self.head_dim,))}
        return out

    def init_decay(self, key):
        """The decay's two vectors: ``A_log = log U(1, 16)`` a head and
        ``dt_bias = softplus^-1(dt)`` with ``log dt ~ U(log 0.001, log 0.1)``
        a channel."""
        k1, k2 = jax.random.split(key)
        dt = jnp.exp(jax.random.uniform(
            k2, (self.num_heads * self.head_dim,), minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))
        return {"A_log": jnp.log(jax.random.uniform(k1, (self.num_heads,), minval=1.0, maxval=16.0)),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt))}  # softplus(dt_bias) = dt

    def _qkv(self, qkv, taps):
        """``(q, k, v)`` as heads in ``qkv``'s dtype: convolution, SiLU, L2 norms."""
        from ..ops.short_conv import conv_silu_heads

        return conv_silu_heads(qkv, taps, self.num_heads, normalise=(True, True, False),
                               scale=(self.head_dim ** -0.5, 1, 1), eps=1e-6)

    def _decay(self, low, f_b, dt_bias, a_log):
        """The log-decay ``(B, H, S, d)`` in float32 from the low-rank projection's first half."""
        b, s, _ = low.shape
        f = jax.nn.softplus((low @ f_b.T).astype(jnp.float32) + dt_bias)
        f = f.reshape(b, s, self.num_heads, self.head_dim).transpose(0, 2, 1, 3)
        return -jnp.exp(a_log.astype(jnp.float32))[:, None, None] * f

    def _gated_norm(self, o, low, g_b, weight):
        """``RMSNorm_head(o) * sigmoid(gate)`` as ``(B, S, P)`` in ``low``'s dtype."""
        b, s, _ = low.shape
        o = rms_normalize(o.astype(jnp.float32), weight, self.eps).transpose(0, 2, 1, 3)
        gate = jax.nn.sigmoid((low @ g_b.T).astype(jnp.float32))
        return (o.reshape(b, s, -1) * gate).astype(low.dtype)

    def apply(self, params, x, **kw):
        from ..ops.kda import chunk_kda

        w = {n: params[n]["weight"].astype(x.dtype)
             for n in ("in_proj", "f_a", "f_b", "g_a", "g_b", "b_proj", "out_proj")}
        # the decay and the output gate are rematerialised: their float32 intermediates
        # are several times the size of what goes in and comes out; ``conv_silu_heads``
        # keeps only what goes in by its own rule
        with jax.named_scope("ht.kda.proj"):
            qkv = x @ w["in_proj"].T
        with jax.named_scope("ht.kda.conv"):
            q, k, v = self._qkv(qkv, params["conv"]["weight"])
        with jax.named_scope("ht.kda.gate"):
            g = jax.checkpoint(self._decay)(x @ w["f_a"].T, w["f_b"], params["dt_bias"], params["A_log"])
            beta = jax.nn.sigmoid((x @ w["b_proj"].T).astype(jnp.float32)).transpose(0, 2, 1)
        o, _ = chunk_kda(q, k, v, g, beta, chunk=self.chunk)  # under ``ht.kda``
        with jax.named_scope("ht.kda.gate"):
            o = jax.checkpoint(self._gated_norm)(o, x @ w["g_a"].T, w["g_b"], params["o_norm"]["weight"])
        with jax.named_scope("ht.kda.proj"):
            return o @ w["out_proj"].T


def lightning_slopes(total_heads: int, layer: int, layers: int):
    """MiniMax-01's decay schedule: ``s_h = 2^(-8 (h + 1) / total_heads) (1 -
    layer / (layers - 1) + 1e-5)`` for every head ``h`` of ``total_heads``,
    ``lambda_h = exp(-s_h)``; ``layer`` of ``layers`` counts from 0."""
    depth = 1.0 - layer / max(layers - 1, 1) + 1e-5
    return [2.0 ** (-8.0 * (h + 1) / total_heads) * depth for h in range(total_heads)]


class LightningAttention(Module):
    """Lightning attention (MiniMax-01's linear attention with a constant decay
    a head) over ``num_heads`` heads of ``head_dim`` (``P = num_heads *
    head_dim``), causal by construction:

    - ``q, k, v = SiLU(x W_in)`` as heads (one input projection to ``3 P``);
      with ``qk_norm`` ``q`` and ``k`` are RMS-normalised a head (a weight
      vector each), then, with ``rope``, rotated by halves (base
      ``rope_base``); ``q`` is scaled by ``head_dim ** -0.5``;
    - ``S_t = lambda_h S_{t-1} + k_t^T v_t``, ``o_t = q_t S_t``
      (:func:`heat_tpu.ops.lightning.lightning_attention`, ``chunk`` tokens at
      a time), ``lambda_h = exp(-s_h)`` from :func:`lightning_slopes` of the
      head's index among ``total_heads`` (this layer holds ``head_offset ..
      head_offset + num_heads - 1`` of them) and of ``layer`` among
      ``layers``;
    - ``out = (RMSNorm_head(o) * sigmoid(x W_g)) W_o``: the norm's statistic is
      a head's, its weight a channel's (``P`` of them).

    No bias.  Scopes: ``ht.lightning`` (the kernel), ``ht.lightning.gate``
    (output norm and gate); the projections, the feature map, the QK norms and
    the rotation run in the caller's scope.  Norms and gates are float32;
    matrices are brought to ``x``'s dtype where they are used.
    """

    def __init__(self, embed_dim: int, num_heads: int, head_dim: int, *, layer: int, layers: int,
                 total_heads: int = None, head_offset: int = 0, qk_norm: bool = True, rope: bool = True,
                 rope_base: float = 10000.0, eps: float = 1e-6, chunk: int = 128):
        total_heads = num_heads if total_heads is None else total_heads
        if not 0 <= head_offset <= total_heads - num_heads:
            raise ValueError(f"heads {head_offset}..{head_offset + num_heads - 1} are not among {total_heads}")
        self.embed_dim, self.num_heads, self.head_dim = embed_dim, num_heads, head_dim
        self.qk_norm, self.rope, self.rope_base, self.eps, self.chunk = qk_norm, rope, rope_base, eps, chunk
        self.slopes = lightning_slopes(total_heads, layer, layers)[head_offset:head_offset + num_heads]

    def init(self, key):
        e, p = self.embed_dim, self.num_heads * self.head_dim
        shapes = {"in_proj": (e, 3 * p), "gate_proj": (e, p), "out_proj": (p, e)}
        out = {name: Linear(*shape, bias=False).init(jax.random.fold_in(key, i))
               for i, (name, shape) in enumerate(shapes.items())}
        if self.qk_norm:
            out["q_norm"] = {"weight": jnp.ones((self.head_dim,))}
            out["k_norm"] = {"weight": jnp.ones((self.head_dim,))}
        out["o_norm"] = {"weight": jnp.ones((p,))}
        return out

    def _heads(self, params, proj):
        """``(q, k, v)`` as ``(B, H, S, d)`` heads from the input projection."""
        from .attention import apply_rope

        b, s, _ = proj.shape
        q, k, v = (t.reshape(b, s, self.num_heads, self.head_dim).transpose(0, 2, 1, 3)
                   for t in jnp.split(jax.nn.silu(proj), 3, axis=-1))
        if self.qk_norm:
            q = rms_normalize(q, params["q_norm"]["weight"], self.eps)
            k = rms_normalize(k, params["k_norm"]["weight"], self.eps)
        if self.rope:
            positions = jnp.arange(s)
            q = apply_rope(q, positions, self.rope_base, "half")
            k = apply_rope(k, positions, self.rope_base, "half")
        return (q * self.head_dim ** -0.5).astype(q.dtype), k, v

    def _gated_norm(self, o, x, w_g, weight):
        """``RMSNorm_head(o) * sigmoid(x W_g)`` as ``(B, S, P)`` in ``x``'s dtype."""
        b, s, _ = x.shape
        o = rms_normalize(o.astype(jnp.float32), None, self.eps).transpose(0, 2, 1, 3).reshape(b, s, -1)
        gate = jax.nn.sigmoid((x @ w_g.T).astype(jnp.float32))
        return (o * weight * gate).astype(x.dtype)

    def apply(self, params, x, **kw):
        from ..ops.lightning import lightning_attention

        w = {n: params[n]["weight"].astype(x.dtype) for n in ("in_proj", "gate_proj", "out_proj")}
        q, k, v = self._heads(params, x @ w["in_proj"].T)
        o = lightning_attention(q, k, v, -jnp.asarray(self.slopes, jnp.float32), chunk=self.chunk)
        with jax.named_scope("ht.lightning.gate"):
            # rematerialised: its float32 intermediates are several times what goes in and out
            o = jax.checkpoint(self._gated_norm)(o, x, w["gate_proj"], params["o_norm"]["weight"])
        return o @ w["out_proj"].T
