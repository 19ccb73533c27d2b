"""Reference-workload model builders.

The reference framework ships no model zoo; its DASO baseline trains
torchvision's ResNet-50 on ImageNet (reference: ``heat/optim/dp_optimizer.py``
docstrings, SURVEY §2.5/§6).  These builders provide the equivalent
residual-CNN family natively so the DASO/DataParallel baselines are
reproducible without torchvision.

What the model layer offers: ``mlp`` and the ``resnet`` family (the
trainers' baselines); ``transformer_encoder`` / ``transformer_decoder`` /
``Seq2SeqTransformer`` and ``TransformerLM`` (LayerNorm + GELU blocks,
learned/rope/sinusoidal positions, GQA, an optional capacity-routed ``MoE``
FFN, KV-cache generation); and ``PatternLM``, a pre-norm causal LM whose
every layer is chosen from the configuration (sequence operator: gated
short convolution, QK-normed rotary GQA, Kimi Delta Attention or latent
attention without positions; feed-forward: SwiGLU or sigmoid-routed
drop-free experts, of which this rank may hold a range, with or without a
shared expert), RMSNorm, tied or untied head, float32 parameters under lower-precision
activations.  ``PatternLM`` trains through ``DataParallel.make_train_step``;
it has no decode path yet.
"""

from __future__ import annotations

from typing import Sequence

from . import modules as nn
from .linear_attention import KimiDeltaAttention, LightningAttention

__all__ = ["resnet", "resnet18", "resnet34", "resnet50", "resnet50_ish", "mlp", "transformer_encoder", "transformer_decoder", "TransformerLM", "Seq2SeqTransformer", "PatternLM"]


def _basic_block(cin: int, cout: int, stride: int = 1) -> nn.Module:
    body = nn.Sequential(
        nn.Conv2d(cin, cout, 3, stride=stride, padding=1, bias=False),
        nn.BatchNorm2d(cout),
        nn.ReLU(),
        nn.Conv2d(cout, cout, 3, stride=1, padding=1, bias=False),
        nn.BatchNorm2d(cout),
    )
    if stride != 1 or cin != cout:
        shortcut = nn.Sequential(
            nn.Conv2d(cin, cout, 1, stride=stride, bias=False), nn.BatchNorm2d(cout)
        )
    else:
        shortcut = None
    return nn.Sequential(nn.Residual(body, shortcut), nn.ReLU())


def _bottleneck_block(cin: int, cmid: int, stride: int = 1, expansion: int = 4) -> nn.Module:
    """ResNet-v1 bottleneck: 1x1 reduce -> 3x3 -> 1x1 expand (x4)."""
    cout = cmid * expansion
    body = nn.Sequential(
        nn.Conv2d(cin, cmid, 1, bias=False),
        nn.BatchNorm2d(cmid),
        nn.ReLU(),
        nn.Conv2d(cmid, cmid, 3, stride=stride, padding=1, bias=False),
        nn.BatchNorm2d(cmid),
        nn.ReLU(),
        nn.Conv2d(cmid, cout, 1, bias=False),
        nn.BatchNorm2d(cout),
    )
    if stride != 1 or cin != cout:
        shortcut = nn.Sequential(
            nn.Conv2d(cin, cout, 1, stride=stride, bias=False), nn.BatchNorm2d(cout)
        )
    else:
        shortcut = None
    return nn.Sequential(nn.Residual(body, shortcut), nn.ReLU())


def resnet(
    stage_sizes: Sequence[int] = (2, 2, 2, 2),
    width: int = 64,
    num_classes: int = 10,
    in_channels: int = 3,
    stem_pool: bool = False,
) -> nn.Module:
    """A ResNet-v1 with BasicBlocks (stage_sizes=(2,2,2,2) ≈ ResNet-18)."""
    layers = [
        nn.Conv2d(in_channels, width, 3, stride=1, padding=1, bias=False),
        nn.BatchNorm2d(width),
        nn.ReLU(),
    ]
    if stem_pool:
        layers.append(nn.MaxPool2d(2))
    cin = width
    for stage, n_blocks in enumerate(stage_sizes):
        cout = width * (2**stage)
        for b in range(n_blocks):
            layers.append(_basic_block(cin, cout, stride=2 if (b == 0 and stage > 0) else 1))
            cin = cout
    layers += [nn.AdaptiveAvgPool2d(1), nn.Flatten(), nn.Linear(cin, num_classes)]
    return nn.Sequential(*layers)


def resnet18(num_classes: int = 10, in_channels: int = 3) -> nn.Module:
    return resnet((2, 2, 2, 2), 64, num_classes, in_channels)


def resnet34(num_classes: int = 1000, in_channels: int = 3) -> nn.Module:
    return resnet((3, 4, 6, 3), 64, num_classes, in_channels, stem_pool=True)


def resnet50(num_classes: int = 1000, in_channels: int = 3, width: int = 64) -> nn.Module:
    """ResNet-50 (bottleneck blocks, (3,4,6,3) stages) — the DASO baseline's
    model (reference trains torchvision resnet50 on ImageNet)."""
    layers = [
        nn.Conv2d(in_channels, width, 7, stride=2, padding=3, bias=False),
        nn.BatchNorm2d(width),
        nn.ReLU(),
        nn.MaxPool2d(3, stride=2),
    ]
    cin = width
    for stage, n_blocks in enumerate((3, 4, 6, 3)):
        cmid = width * (2**stage)
        for b in range(n_blocks):
            layers.append(
                _bottleneck_block(cin, cmid, stride=2 if (b == 0 and stage > 0) else 1)
            )
            cin = cmid * 4
    layers += [nn.AdaptiveAvgPool2d(1), nn.Flatten(), nn.Linear(cin, num_classes)]
    return nn.Sequential(*layers)


# kept for backward compatibility; the honest name is resnet34 (BasicBlocks)
resnet50_ish = resnet34


def mlp(sizes: Sequence[int] = (784, 256, 128, 10)) -> nn.Module:
    """The DataParallel baseline's 3-layer MLP (BASELINE config[3])."""
    layers = []
    for i, (a, b) in enumerate(zip(sizes[:-1], sizes[1:])):
        layers.append(nn.Linear(a, b))
        if i < len(sizes) - 2:
            layers.append(nn.ReLU())
    return nn.Sequential(*layers)


def _ffn(embed_dim: int, mlp_ratio: int) -> nn.Module:
    """THE transformer FFN sub-stack — encoder and decoder blocks share it."""
    return nn.Sequential(
        nn.Linear(embed_dim, mlp_ratio * embed_dim),
        nn.GELU(),
        nn.Linear(mlp_ratio * embed_dim, embed_dim),
    )


def _remat_jit(cache: dict, train: bool, block_fn):
    """Per-train-flag jit(checkpoint(block)) cache — encoder and decoder
    blocks share it.  Rematerializes the block under grad: activations are
    recomputed in the backward pass instead of living in HBM for the whole
    forward — the standard TPU trade of FLOPs for HBM that makes depth x
    sequence-length checkpointing work.  The jit around jax.checkpoint is
    REQUIRED (checkpoint's closed_call cannot evaluate eagerly inside the
    ring path's shard_map) and cached per train flag so repeat applies
    reuse one traced wrapper.  The one thing it keeps is what the flash
    and the sparse kernels name (``ops.flash_attention.KEPT``: their output
    and log-sum-exp; ``ops.sparse_attention.KEPT``: the same and the
    selection), so the backward reads those and never runs an attention
    forward or a selection again; a block without such a call keeps
    nothing."""
    fn = cache.get(train)
    if fn is None:
        import jax

        from ..ops import sparse_attention
        from ..ops.flash_attention import KEPT

        policy = jax.checkpoint_policies.save_only_these_names(*KEPT, *sparse_attention.KEPT)
        fn = cache[train] = jax.jit(jax.checkpoint(block_fn, policy=policy))
    return fn


class _TransformerBlock(nn.Module):
    """Pre-norm transformer encoder block: x + MHA(LN(x)), then
    x + FFN(LN(x)).  ``comm`` routes the attention over the sequence-
    parallel ring (long contexts scale with the mesh).  ``ffn`` swaps the
    dense FFN for any same-shape module — e.g. an expert-parallel
    :class:`~heat_tpu.nn.MoE` (the Switch-transformer block)."""

    def __init__(self, embed_dim: int, num_heads: int, mlp_ratio: int = 4,
                 causal: bool = False, comm=None, remat: bool = False,
                 ffn: nn.Module = None, rope: bool = False,
                 num_kv_heads: int = None, dropout: float = 0.0):
        from .attention import MultiheadAttention

        self.ln1 = nn.LayerNorm(embed_dim)
        self.mha = MultiheadAttention(embed_dim, num_heads, comm=comm, rope=rope,
                                      num_kv_heads=num_kv_heads)
        self.ln2 = nn.LayerNorm(embed_dim)
        self.ff = ffn if ffn is not None else _ffn(embed_dim, mlp_ratio)
        # torch TransformerEncoderLayer's residual-branch dropout sites
        # (after attention, after the FFN); 0 = disabled, eval = identity
        self.drop = nn.Dropout(dropout)
        self.causal = causal
        self.remat = remat
        self._remat_fns = {}  # train -> jitted checkpointed block

    def init(self, key):
        import jax

        k1, k2, k3, k4 = jax.random.split(key, 4)
        return {
            "ln1": self.ln1.init(k1), "mha": self.mha.init(k2),
            "ln2": self.ln2.init(k3), "ff": self.ff.init(k4),
        }

    def _block(self, params, x, k1, k2, train):
        ka = kad = kf = kfd = None
        if k1 is not None:
            import jax

            ka, kad = jax.random.split(k1)
            kf, kfd = jax.random.split(k2)
        a = self.mha.apply(
            params["mha"], self.ln1.apply(params["ln1"], x),
            causal=self.causal, train=train, key=ka,
        )
        h = x + self.drop.apply((), a, train=train, key=kad)
        f = self.ff.apply(
            params["ff"], self.ln2.apply(params["ln2"], h),
            train=train, key=kf,
        )
        return h + self.drop.apply((), f, train=train, key=kfd)

    def apply(self, params, x, *, train: bool = False, key=None):
        k1 = k2 = None
        if key is not None:
            import jax

            k1, k2 = jax.random.split(key)

        if self.remat:
            return _remat_jit(
                self._remat_fns, train,
                lambda p, xx, a, b: self._block(p, xx, a, b, train),
            )(params, x, k1, k2)
        return self._block(params, x, k1, k2, train)

    def init_cache(self, batch: int, max_len: int, dtype=None):
        import jax.numpy as jnp

        return self.mha.init_cache(batch, max_len, dtype or jnp.float32)

    def decode_step(self, params, x, cache):
        """One-token block step against the KV cache: numerically the last
        row of :meth:`apply` over the prefix (causal).  An MoE FFN decodes
        through its drop-free ``decode_apply`` path, so the equality holds
        whenever training-time capacity was not binding (see
        :meth:`MoE.decode_apply`)."""
        a, cache = self.mha.decode_step(
            params["mha"], self.ln1.apply(params["ln1"], x), cache
        )
        h = x + a
        ff = getattr(self.ff, "decode_apply", self.ff.apply)
        return h + ff(params["ff"], self.ln2.apply(params["ln2"], h)), cache


def _block_ffn(embed_dim: int, mlp_ratio: int, num_experts, moe_top_k: int,
               comm, capacity_factor: float = 1.5):
    """Dense FFN, or an expert-parallel MoE of the same hidden width when
    ``num_experts`` is set (the Switch-transformer block)."""
    if not num_experts:
        return None  # _TransformerBlock builds the dense FFN
    from .moe import MoE

    return MoE(embed_dim, num_experts, hidden_dim=mlp_ratio * embed_dim,
               top_k=moe_top_k, capacity_factor=capacity_factor, comm=comm)


def transformer_encoder(
    embed_dim: int = 256,
    num_heads: int = 8,
    depth: int = 4,
    mlp_ratio: int = 4,
    causal: bool = False,
    comm=None,
    remat: bool = False,
    num_experts: int = None,
    moe_top_k: int = 2,
    moe_capacity_factor: float = 1.5,
    dropout: float = 0.0,
) -> nn.Module:
    """A stack of pre-norm transformer blocks over (B, S, embed_dim) input.

    Bidirectional by default (torch ``TransformerEncoder`` convention);
    pass ``causal=True`` for decoder-style masked attention.

    Beyond-reference model family (the reference predates transformers —
    SURVEY §2.8 honest-scope note), built entirely from this framework's
    native modules; with ``comm`` every block's attention runs
    sequence-parallel on the mesh ring, so context length scales with the
    chip count.  ``remat=True`` wraps each block in ``jax.checkpoint`` so
    training recomputes block activations in the backward pass instead of
    holding depth × (B, S, E) of them in HBM — combine with the flash
    local kernel (which already never materializes (S, S)) for the full
    long-context memory story.  ``num_experts`` swaps every block's FFN
    for an expert-parallel :class:`~heat_tpu.nn.MoE` of the same hidden
    width (Switch-transformer style; ``comm`` shards the experts too).
    """
    # ONE shared (stateless) MoE instance for all blocks: params are still
    # per-block via each block's init key, but the identity-keyed compiled
    # EP program is built once instead of depth times
    moe_ffn = _block_ffn(embed_dim, mlp_ratio, num_experts, moe_top_k, comm,
                         moe_capacity_factor)
    return nn.Sequential(
        *[_TransformerBlock(embed_dim, num_heads, mlp_ratio, causal, comm,
                            remat=remat, ffn=moe_ffn, dropout=dropout)
          for _ in range(depth)]
    )


def _sinusoidal_positions(positions, embed_dim: int):
    """The original transformer's fixed sin/cos position code, computed on
    the fly (no parameters, defined for ANY position — unlike a learned
    table it never runs out).  ``positions`` broadcasts like in
    :func:`heat_tpu.nn.apply_rope`: an arange for a sequence, a scalar for
    one decode step."""
    import jax.numpy as jnp

    half = embed_dim // 2
    div = 10000.0 ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.asarray(positions, jnp.float32)[..., None] / div  # (..., half)
    return jnp.stack([jnp.sin(ang), jnp.cos(ang)], axis=-1).reshape(
        *ang.shape[:-1], 2 * half
    )


def _gen_program(model, cache_key, build):
    """Per-instance LRU of compiled generation programs — ONE policy for
    every decoding model (LM and seq2seq): keyed on static shapes only,
    bounded because each distinct total length compiles its own scan
    executable."""
    from collections import OrderedDict

    progs = model.__dict__.setdefault("_gen_programs", OrderedDict())
    fn = progs.get(cache_key)
    if fn is None:
        fn = progs[cache_key] = build()
        if len(progs) > 16:
            progs.popitem(last=False)
    else:
        progs.move_to_end(cache_key)
    return fn


def _normalize_truncation(top_k, top_p, vocab_size, sampled):
    """Validate + canonicalize the truncation knobs BEFORE they enter the
    program-cache key, so no-op values never fork a duplicate executable:
    greedy decoding ignores truncation entirely; ``top_k`` of 0/None or
    >= vocab disables it (the transformers convention); ``top_p`` of
    None or >= 1 disables it.  Invalid values raise eagerly."""
    if not sampled:
        return None, None
    if top_k is not None:
        top_k = int(top_k)
        if top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {top_k}")
        if top_k == 0 or top_k >= vocab_size:
            top_k = None
    if top_p is not None:
        top_p = float(top_p)
        if top_p <= 0.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        if top_p >= 1.0:
            top_p = None
    return top_k, top_p


def _next_token(logits, sampled, temp, k, top_k=None, top_p=None):
    """Greedy-or-sampled next token — the one sampling rule both decode
    scans share.  ``top_k`` keeps only the k highest-probability tokens;
    ``top_p`` keeps the smallest nucleus whose probability mass reaches p
    (the highest-probability token always survives).  Both are static
    (part of the compiled program)."""
    import jax
    import jax.numpy as jnp

    if not sampled:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), k
    logits = logits / temp
    if top_k is not None and top_k < logits.shape[-1]:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p is not None and top_p < 1.0:
        # cut by sorted RANK, not by logit value: a value threshold drops
        # in-nucleus tokens that happen to tie the largest cut logit
        # (boundary ties would truncate more than the nucleus).  Slots whose
        # mass STRICTLY before them already reaches p are cut — a suffix of
        # the descending order; the top slot's preceding mass is 0, so it
        # always survives (no degenerate all-masked row even for tiny p)
        order = jnp.argsort(-logits, axis=-1)  # descending
        srt = jnp.take_along_axis(logits, order, axis=-1)
        probs = jax.nn.softmax(srt, axis=-1)
        before = jnp.cumsum(probs, axis=-1) - probs
        keep_sorted = before < top_p
        # scatter the sorted-space mask back to vocab order: token v sits at
        # sorted slot inv[v] = rank of v
        inv = jnp.argsort(order, axis=-1)
        keep = jnp.take_along_axis(keep_sorted, inv, axis=-1)
        logits = jnp.where(keep, logits, -jnp.inf)
    k, sub = jax.random.split(k)
    return jax.random.categorical(sub, logits, axis=-1).astype(jnp.int32), k


class TransformerLM(nn.Module):
    """GPT-style causal language model: token embedding + positions
    (``positions='learned'`` table, the default; ``'rope'`` rotary — see
    :func:`heat_tpu.nn.apply_rope`; or parameter-free ``'sinusoidal'``)
    + causal transformer
    blocks + final LayerNorm + LM head (untied by default;
    ``tie_embeddings=True`` shares the token-embedding matrix and drops
    ``params['head']``), with a compiled KV-cache ``generate`` loop.

    Beyond-reference model family (same provenance note as
    :func:`transformer_encoder`), completing the inference half of the
    transformer story: ``apply`` is the teacher-forced training forward;
    :meth:`generate` is TPU-idiom autoregressive decoding — a static
    (B, H, max_len, d) KV cache per block updated by dynamic slices inside
    ONE ``lax.scan`` program, so a whole generation is a single XLA
    dispatch (no per-token host round-trips, no shape growth, no
    retracing).  ``comm``/``remat`` thread through to the blocks for
    sequence-parallel / checkpointed TRAINING; decoding is single-mesh
    (the (1, L) per-step attention has no sequence axis to shard).
    """

    def __init__(self, vocab_size: int, embed_dim: int = 256, num_heads: int = 8,
                 depth: int = 4, mlp_ratio: int = 4, max_len: int = 1024,
                 comm=None, remat: bool = False, num_experts: int = None,
                 moe_top_k: int = 2, moe_capacity_factor: float = 1.5,
                 positions: str = "learned", tie_embeddings: bool = False,
                 num_kv_heads: int = None, dropout: float = 0.0):
        if positions not in ("learned", "rope", "sinusoidal"):
            raise ValueError(
                f"positions must be 'learned', 'rope' or 'sinusoidal', got {positions!r}"
            )
        if positions == "sinusoidal" and embed_dim % 2:
            raise ValueError("sinusoidal positions require an even embed_dim")
        self.tie_embeddings = tie_embeddings
        self.vocab_size = vocab_size
        self.embed_dim = embed_dim
        self.max_len = max_len
        self.positions = positions
        self.embed = nn.Embedding(vocab_size, embed_dim)
        # one shared MoE instance (stateless) -> one compiled EP program
        moe_ffn = _block_ffn(embed_dim, mlp_ratio, num_experts, moe_top_k,
                             comm, moe_capacity_factor)
        self.blocks = [
            _TransformerBlock(embed_dim, num_heads, mlp_ratio, causal=True,
                              comm=comm, remat=remat, ffn=moe_ffn,
                              rope=(positions == "rope"),
                              num_kv_heads=num_kv_heads, dropout=dropout)
            for _ in range(depth)
        ]
        self.ln_f = nn.LayerNorm(embed_dim)
        if not tie_embeddings:
            self.head = nn.Linear(embed_dim, vocab_size, bias=False)

    def init(self, key):
        import jax
        import jax.numpy as jnp

        keys = jax.random.split(key, len(self.blocks) + 4)
        scale = 1.0 / (self.embed_dim**0.5)
        out = {
            "embed": jax.tree.map(lambda a: a * scale, self.embed.init(keys[0])),
            "blocks": [b.init(k) for b, k in zip(self.blocks, keys[2:])],
            "ln_f": self.ln_f.init(keys[-2]),
        }
        if not self.tie_embeddings:
            out["head"] = self.head.init(keys[-1])
        if self.positions == "learned":
            out["pos"] = scale * jax.random.normal(keys[1], (self.max_len, self.embed_dim))
        return out

    def _logits(self, params, h):
        """LM head: the head module, or the TRANSPOSED token embedding
        when ``tie_embeddings`` (GPT-2 style — one (V, E) matrix serves
        both ends, and its gradient accumulates from both uses; the tied
        matmul matches the bias-free head module's semantics)."""
        if self.tie_embeddings:
            return h @ params["embed"]["weight"].T
        return self.head.apply(params["head"], h)

    def apply(self, params, tokens, *, train: bool = False, key=None):
        """Teacher-forced forward: tokens (B, S) int → logits (B, S, vocab)."""
        import jax

        S = tokens.shape[1]
        if S > self.max_len:
            raise ValueError(f"sequence length {S} exceeds max_len {self.max_len}")
        h = self.embed.apply(params["embed"], tokens)
        if self.positions == "learned":
            h = h + params["pos"][:S]
        elif self.positions == "sinusoidal":
            import jax.numpy as jnp

            h = h + _sinusoidal_positions(jnp.arange(S), self.embed_dim).astype(h.dtype)
        for b, p in zip(self.blocks, params["blocks"]):
            sub = None
            if key is not None:
                key, sub = jax.random.split(key)
            h = b.apply(p, h, train=train, key=sub)
        return self._logits(params, self.ln_f.apply(params["ln_f"], h))

    def decode_step(self, params, tok, pos, caches):
        """Logits for one position given the caches: tok (B,) int at
        position ``pos``.  Returns (logits (B, vocab), new_caches).

        Under ``positions='rope'`` the rotation position comes from the
        CACHE index (which the caches advance themselves); ``pos`` selects
        the learned-table row or the sinusoidal code in the other modes —
        keep them in step by feeding positions 0,1,2,… from fresh caches
        (as ``generate`` does); resuming mid-sequence needs caches whose
        index already equals ``pos``."""
        h = self.embed.apply(params["embed"], tok[:, None])
        if self.positions == "learned":
            h = h + params["pos"][pos]
        elif self.positions == "sinusoidal":
            h = h + _sinusoidal_positions(pos, self.embed_dim).astype(h.dtype)
        new = []
        for b, p, c in zip(self.blocks, params["blocks"], caches):
            h, c = b.decode_step(p, h, c)
            new.append(c)
        logits = self._logits(params, self.ln_f.apply(params["ln_f"], h))
        return logits[:, 0, :], new

    def generate(self, params, prompt, max_new_tokens: int, *,
                 temperature: float = 0.0, top_k: int = None,
                 top_p: float = None, eos_id: int = None, key=None):
        """Autoregressive continuation of ``prompt`` (B, S0) int tokens.

        ``temperature=0`` decodes greedily; otherwise softmax sampling at
        the given temperature (requires ``key``), optionally truncated to
        the ``top_k`` highest-probability tokens and/or the ``top_p``
        nucleus (static — part of the compiled program).  ``eos_id`` pins
        a sequence to EOS once it emits it (prompt-phase EOS tokens never
        stop a sequence).  The prompt is consumed through the same cached
        step as generation — the whole thing is ONE jitted ``lax.scan``
        program, LRU-cached on the model instance and keyed on (batch,
        total length, sampled?, top_k, top_p, eos used?) — the prompt
        length, temperature and eos VALUE ride in as DYNAMIC arguments,
        so a serving loop with naturally varying prompt lengths,
        temperatures or stop tokens reuses one executable (truncation
        knobs are canonicalized so no-op values never fork a duplicate
        program).
        Returns (B, S0 + max_new_tokens) tokens beginning with the prompt.
        """
        import functools

        import jax
        import jax.numpy as jnp

        sampled = bool(temperature)
        if sampled and key is None:
            raise ValueError("sampling (temperature > 0) requires key=")
        B, S0 = prompt.shape
        n_new = int(max_new_tokens)
        total = S0 + n_new
        if total > self.max_len:
            raise ValueError(
                f"prompt + max_new_tokens = {total} exceeds max_len {self.max_len}"
            )
        top_k, top_p = _normalize_truncation(top_k, top_p, self.vocab_size, sampled)
        has_eos = eos_id is not None
        if has_eos and not 0 <= int(eos_id) < self.vocab_size:
            raise ValueError(f"eos_id {eos_id} outside vocab [0, {self.vocab_size})")
        fn = _gen_program(self, (B, total, sampled, top_k, top_p, has_eos),
                          lambda: jax.jit(functools.partial(
                              self._generate_scan, total=total, sampled=sampled,
                              top_k=top_k, top_p=top_p, has_eos=has_eos)))
        ys0 = jnp.concatenate(
            [prompt.astype(jnp.int32), jnp.zeros((B, n_new), jnp.int32)], axis=1
        )
        return fn(
            params,
            ys0,
            jnp.asarray(S0, jnp.int32),
            jnp.asarray(temperature if sampled else 1.0, jnp.float32),
            jnp.asarray(eos_id if has_eos else -1, jnp.int32),
            key if key is not None else jax.random.key(0),
        )

    def _generate_scan(self, params, ys, S0, temp, eos, key, *, total, sampled,
                       top_k=None, top_p=None, has_eos=False):
        import jax
        import jax.numpy as jnp
        from jax import lax

        B = ys.shape[0]
        # cache in the model's compute dtype (bf16 params -> bf16 K/V
        # buffers and attention einsums, halving the decode working set)
        dt = params["embed"]["weight"].dtype
        caches = [b.init_cache(B, total, dt) for b in self.blocks]

        def step(carry, t):
            ys, caches, done, k = carry
            logits, caches = self.decode_step(params, ys[:, t], t, caches)
            nxt, k = _next_token(logits, sampled, temp, k, top_k, top_p)
            # prompt positions keep their given token; generation begins
            # at index S0 (fed by the prediction from position S0-1)
            gen = t + 1 >= S0
            cur = lax.dynamic_slice_in_dim(ys, t + 1, 1, axis=1)[:, 0]
            nxt = jnp.where(gen, nxt, cur)
            if has_eos:
                # finished sequences stay pinned to EOS; prompt-phase EOS
                # tokens never mark a sequence finished
                nxt = jnp.where(done, eos, nxt)
                done = done | (gen & (nxt == eos))
            ys = lax.dynamic_update_slice_in_dim(ys, nxt[:, None], t + 1, axis=1)
            return (ys, caches, done, k), None

        done0 = jnp.zeros((B,), bool)
        (ys, _, _, _), _ = lax.scan(
            step, (ys, caches, done0, key), jnp.arange(total - 1)
        )
        return ys


class _TransformerDecoderBlock(nn.Module):
    """Pre-norm transformer DECODER block: x + SelfMHA(LN(x), causal),
    then x + CrossMHA(LN(x), kv=memory), then x + FFN(LN(x)).  With
    ``comm`` both attentions run on the sequence-parallel ring — the
    causal self-attention over the decoder sequence AND the rectangular
    cross-attention against the (differently-sized) encoder memory."""

    def __init__(self, embed_dim: int, num_heads: int, mlp_ratio: int = 4,
                 comm=None, remat: bool = False, ffn: nn.Module = None,
                 dropout: float = 0.0):
        from .attention import MultiheadAttention

        self.ln1 = nn.LayerNorm(embed_dim)
        self.self_attn = MultiheadAttention(embed_dim, num_heads, comm=comm)
        self.ln2 = nn.LayerNorm(embed_dim)
        self.cross_attn = MultiheadAttention(embed_dim, num_heads, comm=comm)
        self.ln3 = nn.LayerNorm(embed_dim)
        self.ff = ffn if ffn is not None else _ffn(embed_dim, mlp_ratio)
        self.drop = nn.Dropout(dropout)  # torch residual-branch sites
        self.remat = remat
        self._remat_fns = {}

    def init(self, key):
        import jax

        ks = jax.random.split(key, 6)
        return {
            "ln1": self.ln1.init(ks[0]), "self_attn": self.self_attn.init(ks[1]),
            "ln2": self.ln2.init(ks[2]), "cross_attn": self.cross_attn.init(ks[3]),
            "ln3": self.ln3.init(ks[4]), "ff": self.ff.init(ks[5]),
        }

    def _block(self, params, x, memory, k1, k2, train):
        ka = kad = kcd = kf = kfd = None
        if k1 is not None:
            import jax

            ka, kad, kcd = jax.random.split(k1, 3)
            kf, kfd = jax.random.split(k2)
        a = self.self_attn.apply(
            params["self_attn"], self.ln1.apply(params["ln1"], x),
            causal=True, train=train, key=ka,
        )
        h = x + self.drop.apply((), a, train=train, key=kad)
        c = self.cross_attn.apply(
            params["cross_attn"], self.ln2.apply(params["ln2"], h),
            kv=memory, train=train,
        )
        h = h + self.drop.apply((), c, train=train, key=kcd)
        f = self.ff.apply(
            params["ff"], self.ln3.apply(params["ln3"], h),
            train=train, key=kf,
        )
        return h + self.drop.apply((), f, train=train, key=kfd)

    def apply(self, params, x, memory, *, train: bool = False, key=None):
        k1 = k2 = None
        if key is not None:
            import jax

            k1, k2 = jax.random.split(key)
        if self.remat:
            return _remat_jit(
                self._remat_fns, train,
                lambda p, xx, mm, a, b: self._block(p, xx, mm, a, b, train),
            )(params, x, memory, k1, k2)
        return self._block(params, x, memory, k1, k2, train)

    def decode_state(self, params, memory, batch: int, max_len: int, dtype=None):
        """Per-block decoding state: an empty self-attention KV cache plus
        the memory's cross-attention K/V, projected ONCE."""
        import jax.numpy as jnp

        kh, vh = self.cross_attn.precompute_kv(params["cross_attn"], memory)
        return {
            "self": self.self_attn.init_cache(batch, max_len, dtype or jnp.float32),
            "mem_k": kh,
            "mem_v": vh,
        }

    def decode_step(self, params, x, state):
        """One-token decoder block step: cached causal self-attention, then
        cross-attention against the precomputed memory K/V, then the FFN —
        numerically the last row of :meth:`apply` over the prefix."""
        a, self_cache = self.self_attn.decode_step(
            params["self_attn"], self.ln1.apply(params["ln1"], x), state["self"]
        )
        h = x + a
        h = h + self.cross_attn.cross_step(
            params["cross_attn"], self.ln2.apply(params["ln2"], h),
            state["mem_k"], state["mem_v"],
        )
        ff = getattr(self.ff, "decode_apply", self.ff.apply)
        out = h + ff(params["ff"], self.ln3.apply(params["ln3"], h))
        return out, {**state, "self": self_cache}


class _TransformerDecoder(nn.Module):
    """Stack of decoder blocks sharing one encoder ``memory``."""

    def __init__(self, blocks):
        self.blocks = blocks

    def init(self, key):
        import jax

        keys = jax.random.split(key, max(len(self.blocks), 1))
        return [b.init(k) for b, k in zip(self.blocks, keys)]

    def apply(self, params, x, memory, *, train: bool = False, key=None):
        import jax

        for b, p in zip(self.blocks, params):
            sub = None
            if key is not None:
                key, sub = jax.random.split(key)
            x = b.apply(p, x, memory, train=train, key=sub)
        return x


def transformer_decoder(
    embed_dim: int = 256,
    num_heads: int = 8,
    depth: int = 4,
    mlp_ratio: int = 4,
    comm=None,
    remat: bool = False,
    num_experts: int = None,
    moe_top_k: int = 2,
    moe_capacity_factor: float = 1.5,
    dropout: float = 0.0,
) -> nn.Module:
    """A stack of pre-norm transformer DECODER blocks: causal
    self-attention + cross-attention against an encoder ``memory``.

    ``apply(params, x, memory)`` with ``x`` (B, S_dec, E) and ``memory``
    (B, S_enc, E) — the two sequence lengths are independent.  With
    ``comm`` every block's attentions run sequence-parallel on the mesh
    ring (the cross-attention rotates the encoder memory's K/V blocks
    against resident decoder query blocks), so BOTH context lengths scale
    with the chip count; ``remat=True`` checkpoints each block.
    ``num_experts`` swaps every block's FFN for an expert-parallel
    :class:`~heat_tpu.nn.MoE` of the same hidden width (Switch style;
    ``moe_top_k``/``moe_capacity_factor`` tune the routing).  Beyond-
    reference model family, same provenance note as
    :func:`transformer_encoder`.
    """
    moe_ffn = _block_ffn(embed_dim, mlp_ratio, num_experts, moe_top_k, comm,
                         moe_capacity_factor)
    return _TransformerDecoder([
        _TransformerDecoderBlock(embed_dim, num_heads, mlp_ratio, comm,
                                 remat=remat, ffn=moe_ffn, dropout=dropout)
        for _ in range(depth)
    ])


class Seq2SeqTransformer(nn.Module):
    """Encoder-decoder transformer (the torch ``nn.Transformer`` shape):
    source embedding + bidirectional encoder, target embedding + causal
    decoder with cross-attention, LM head — plus cached seq2seq
    ``generate``.

    Beyond-reference model family (same provenance note as
    :func:`transformer_encoder`).  ``apply(params, src, tgt)`` is the
    teacher-forced forward over token ids; :meth:`generate` encodes the
    source ONCE, projects each decoder block's cross-attention K/V from
    the memory ONCE, and then runs the whole autoregressive loop as one
    jitted ``lax.scan`` over static self-attention caches — the same TPU
    decode idiom as :class:`TransformerLM`.
    """

    def __init__(self, src_vocab: int, tgt_vocab: int, embed_dim: int = 256,
                 num_heads: int = 8, enc_depth: int = 4, dec_depth: int = 4,
                 mlp_ratio: int = 4, max_len: int = 1024, comm=None,
                 remat: bool = False, num_experts: int = None,
                 moe_top_k: int = 2, moe_capacity_factor: float = 1.5,
                 dropout: float = 0.0):
        self.src_vocab = src_vocab
        self.tgt_vocab = tgt_vocab
        self.embed_dim = embed_dim
        self.max_len = max_len
        self.src_embed = nn.Embedding(src_vocab, embed_dim)
        self.tgt_embed = nn.Embedding(tgt_vocab, embed_dim)
        # ONE shared MoE for both stacks (stateless; params are per-block
        # via init keys) -> one compiled EP program for the whole model
        moe_ffn = _block_ffn(embed_dim, mlp_ratio, num_experts, moe_top_k,
                             comm, moe_capacity_factor)
        self.encoder = [
            _TransformerBlock(embed_dim, num_heads, mlp_ratio, causal=False,
                              comm=comm, remat=remat, ffn=moe_ffn,
                              dropout=dropout)
            for _ in range(enc_depth)
        ]
        self.decoder = [
            _TransformerDecoderBlock(embed_dim, num_heads, mlp_ratio, comm,
                                     remat=remat, ffn=moe_ffn,
                                     dropout=dropout)
            for _ in range(dec_depth)
        ]
        self.ln_f = nn.LayerNorm(embed_dim)
        self.head = nn.Linear(embed_dim, tgt_vocab, bias=False)

    def init(self, key):
        import jax
        import jax.numpy as jnp

        n = len(self.encoder) + len(self.decoder)
        keys = jax.random.split(key, n + 5)
        scale = 1.0 / (self.embed_dim**0.5)
        ne = len(self.encoder)
        return {
            "src_embed": jax.tree.map(lambda a: a * scale, self.src_embed.init(keys[0])),
            "tgt_embed": jax.tree.map(lambda a: a * scale, self.tgt_embed.init(keys[1])),
            "pos": scale * jax.random.normal(keys[2], (self.max_len, self.embed_dim)),
            "encoder": [b.init(k) for b, k in zip(self.encoder, keys[3 : 3 + ne])],
            "decoder": [b.init(k) for b, k in zip(self.decoder, keys[3 + ne : 3 + n])],
            "ln_f": self.ln_f.init(keys[-2]),
            "head": self.head.init(keys[-1]),
        }

    def encode(self, params, src, *, train: bool = False, key=None):
        """src (B, S_enc) int → memory (B, S_enc, E)."""
        import jax

        S = src.shape[1]
        if S > self.max_len:
            raise ValueError(f"source length {S} exceeds max_len {self.max_len}")
        h = self.src_embed.apply(params["src_embed"], src) + params["pos"][:S]
        for b, p in zip(self.encoder, params["encoder"]):
            sub = None
            if key is not None:
                key, sub = jax.random.split(key)
            h = b.apply(p, h, train=train, key=sub)
        return h

    def apply(self, params, src, tgt, *, train: bool = False, key=None):
        """Teacher-forced forward: (src, tgt) token ids → logits over the
        target vocabulary at every target position."""
        import jax

        enc_key = dec_key = None
        if key is not None:
            enc_key, dec_key = jax.random.split(key)
        memory = self.encode(params, src, train=train, key=enc_key)
        S = tgt.shape[1]
        if S > self.max_len:
            raise ValueError(f"target length {S} exceeds max_len {self.max_len}")
        h = self.tgt_embed.apply(params["tgt_embed"], tgt) + params["pos"][:S]
        for b, p in zip(self.decoder, params["decoder"]):
            sub = None
            if dec_key is not None:
                dec_key, sub = jax.random.split(dec_key)
            h = b.apply(p, h, memory, train=train, key=sub)
        return self.head.apply(params["head"], self.ln_f.apply(params["ln_f"], h))

    def decode_step(self, params, tok, pos, states):
        """Logits for one target position given per-block decode states."""
        h = self.tgt_embed.apply(params["tgt_embed"], tok[:, None]) + params["pos"][pos]
        new = []
        for b, p, s in zip(self.decoder, params["decoder"], states):
            h, s = b.decode_step(p, h, s)
            new.append(s)
        logits = self.head.apply(params["head"], self.ln_f.apply(params["ln_f"], h))
        return logits[:, 0, :], new

    def generate(self, params, src, max_new_tokens: int, *, bos_id: int = 0,
                 temperature: float = 0.0, top_k: int = None,
                 top_p: float = None, eos_id: int = None, key=None):
        """Autoregressively decode a target sequence for ``src`` (B, S_enc)
        starting from ``bos_id``: encode once, then one fused scan.
        ``temperature``/``top_k``/``top_p``/``eos_id`` behave exactly as in
        :meth:`TransformerLM.generate` (EOS pins finished sequences; its
        value is dynamic, truncation knobs are static and canonicalized).
        Returns (B, 1 + max_new_tokens) target tokens beginning with BOS.
        """
        import functools

        import jax
        import jax.numpy as jnp

        sampled = bool(temperature)
        if sampled and key is None:
            raise ValueError("sampling (temperature > 0) requires key=")
        B = src.shape[0]
        n_new = int(max_new_tokens)
        if 1 + n_new > self.max_len:
            raise ValueError(f"1 + max_new_tokens = {1 + n_new} exceeds max_len {self.max_len}")
        top_k, top_p = _normalize_truncation(top_k, top_p, self.tgt_vocab, sampled)
        has_eos = eos_id is not None
        if has_eos and not 0 <= int(eos_id) < self.tgt_vocab:
            raise ValueError(f"eos_id {eos_id} outside vocab [0, {self.tgt_vocab})")
        fn = _gen_program(self, (B, src.shape[1], n_new, sampled, top_k, top_p, has_eos),
                          lambda: jax.jit(functools.partial(
                              self._generate_scan, n_new=n_new, sampled=sampled,
                              top_k=top_k, top_p=top_p, has_eos=has_eos)))
        return fn(
            params,
            src,
            jnp.asarray(bos_id, jnp.int32),
            jnp.asarray(temperature if sampled else 1.0, jnp.float32),
            jnp.asarray(eos_id if has_eos else -1, jnp.int32),
            key if key is not None else jax.random.key(0),
        )

    def _decode_init(self, params, src, total, beams: int = 1):
        """Per-block decode states for ``src`` — THE shared setup of the
        greedy/sampled scan and the beam scan.  The encoder runs ONCE and
        each block's cross-attention K/V is projected from the un-repeated
        (B, ...) memory; with ``beams > 1`` the projected K/V is repeated
        beam-major afterwards (one cheap copy instead of W projections)
        while the self-attention caches are sized B·beams directly."""
        import jax.numpy as jnp

        B = src.shape[0]
        memory = self.encode(params, src)
        states = []
        for b, p in zip(self.decoder, params["decoder"]):
            st = b.decode_state(p, memory, B * beams, total, params["pos"].dtype)
            if beams > 1:
                st = {**st,
                      "mem_k": jnp.repeat(st["mem_k"], beams, axis=0),
                      "mem_v": jnp.repeat(st["mem_v"], beams, axis=0)}
            states.append(st)
        return states

    def _generate_scan(self, params, src, bos, temp, eos, key, *, n_new, sampled,
                       top_k=None, top_p=None, has_eos=False):
        import jax
        import jax.numpy as jnp
        from jax import lax

        B = src.shape[0]
        total = 1 + n_new
        states = self._decode_init(params, src, total)
        ys = jnp.concatenate(
            [jnp.full((B, 1), bos, jnp.int32), jnp.zeros((B, n_new), jnp.int32)],
            axis=1,
        )

        def step(carry, t):
            ys, states, done, k = carry
            logits, states = self.decode_step(params, ys[:, t], t, states)
            nxt, k = _next_token(logits, sampled, temp, k, top_k, top_p)
            if has_eos:
                nxt = jnp.where(done, eos, nxt)
                done = done | (nxt == eos)
            ys = lax.dynamic_update_slice_in_dim(ys, nxt[:, None], t + 1, axis=1)
            return (ys, states, done, k), None

        done0 = jnp.zeros((B,), bool)
        (ys, _, _, _), _ = lax.scan(
            step, (ys, states, done0, key), jnp.arange(total - 1)
        )
        return ys

    # ------------------------------------------------------------------ #
    # beam search
    # ------------------------------------------------------------------ #

    def beam_search(self, params, src, max_new_tokens: int, *,
                    beam_width: int = 4, bos_id: int = 0, eos_id: int = None,
                    length_penalty: float = 0.0):
        """Beam search over the target vocabulary.

        Keeps the ``beam_width`` highest-log-probability partial sequences
        at every step; the whole search is ONE jitted ``lax.scan`` — beams
        ride the batch dimension (B·W), and each step reorders the beams'
        KV caches by a batched gather.  Returns the single best sequence
        per source, (B, 1 + max_new_tokens) starting with BOS.

        Without ``eos_id`` sequences are fixed-length: scores compare
        completions of identical length, so no length normalization is
        needed.  With ``eos_id``, a beam that emits EOS is *finished*: its
        only continuation re-emits EOS at log-probability 0 (the cumulative
        score freezes, and the tail is EOS-padded — the same padding
        contract as :meth:`generate` with ``eos_id``), and its generated
        length (counting the EOS token itself) is recorded.  Final ranking
        divides each beam's score by ``length ** length_penalty``
        (``length_penalty=0``, the default, ranks by raw score; larger
        values favour longer completions, as in GNMT-style decoding).
        ``beam_width=1`` is exactly greedy decoding, with or without EOS
        (tested).
        """
        import functools

        import jax

        B = src.shape[0]
        n_new = int(max_new_tokens)
        W = int(beam_width)
        if W < 1:
            raise ValueError(f"beam_width must be >= 1, got {W}")
        if 1 + n_new > self.max_len:
            raise ValueError(f"1 + max_new_tokens = {1 + n_new} exceeds max_len {self.max_len}")
        has_eos = eos_id is not None
        if has_eos and not 0 <= int(eos_id) < self.tgt_vocab:
            raise ValueError(f"eos_id {eos_id} outside vocab [0, {self.tgt_vocab})")
        lp = float(length_penalty)
        if lp != 0.0 and not has_eos:
            raise ValueError("length_penalty requires eos_id (fixed-length "
                             "beams all share one length)")
        # length_penalty is a TRACED scalar (like the eos value): sweeping
        # the GNMT alpha reuses one executable per (B, S, n_new, W, has_eos)
        fn = _gen_program(self, ("beam", B, src.shape[1], n_new, W, has_eos),
                          lambda: jax.jit(functools.partial(
                              self._beam_scan, n_new=n_new, W=W,
                              has_eos=has_eos)))
        import jax.numpy as jnp

        eos = jnp.asarray(-1 if eos_id is None else eos_id, jnp.int32)
        return fn(params, src, jnp.asarray(bos_id, jnp.int32), eos,
                  jnp.asarray(lp, jnp.float32))

    def _beam_scan(self, params, src, bos, eos, length_penalty, *, n_new, W,
                   has_eos=False):
        import jax
        import jax.numpy as jnp
        from jax import lax

        B = src.shape[0]
        V = self.tgt_vocab
        total = 1 + n_new
        states = self._decode_init(params, src, total, beams=W)
        ys = jnp.concatenate(
            [jnp.full((B * W, 1), bos, jnp.int32),
             jnp.zeros((B * W, n_new), jnp.int32)], axis=1
        )
        # only beam 0 is live at the start, or the first expansion would
        # pick W copies of the same argmax token
        scores = jnp.where(jnp.arange(W) == 0, 0.0, -jnp.inf)[None, :].repeat(B, 0)
        done = jnp.zeros((B, W), bool)
        lengths = jnp.zeros((B, W), jnp.int32)

        def reorder(a, gather_idx):
            # beam-reorder the self-cache K/V (leading dim B*W); the scalar
            # write index is shared, and the memory K/V never needs the
            # gather — beams of one source share identical memory rows
            if getattr(a, "ndim", 0) >= 1 and a.shape[0] == B * W:
                return a[gather_idx]
            return a

        def step(carry, t):
            ys, states, scores, done, lengths = carry
            logits, states = self.decode_step(params, ys[:, t], t, states)
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            logp = logp.reshape(B, W, V)
            if has_eos:
                # a finished beam's single legal continuation is EOS at
                # log-prob 0: the beam survives top-k with a frozen score
                # instead of forking into W phantom copies of itself
                frozen = jnp.where(jnp.arange(V) == eos, 0.0, -jnp.inf)
                logp = jnp.where(done[:, :, None], frozen[None, None, :], logp)
            cand = scores[:, :, None] + logp  # (B, W, V)
            top_s, top_i = lax.top_k(cand.reshape(B, W * V), W)  # (B, W)
            beam_of = top_i // V
            tok = (top_i % V).astype(jnp.int32)
            gather_idx = (jnp.arange(B)[:, None] * W + beam_of).reshape(-1)
            ys = ys[gather_idx]
            ys = lax.dynamic_update_slice_in_dim(
                ys, tok.reshape(-1)[:, None], t + 1, axis=1
            )
            if has_eos:
                done_g = jnp.take_along_axis(done, beam_of, axis=1)
                len_g = jnp.take_along_axis(lengths, beam_of, axis=1)
                lengths = jnp.where(done_g, len_g, len_g + 1)
                done = done_g | (tok == eos)
            states = [
                {**st, "self": jax.tree.map(lambda a: reorder(a, gather_idx),
                                            st["self"])}
                for st in states
            ]
            return (ys, states, top_s, done, lengths), None

        (ys, _, scores, done, lengths), _ = lax.scan(
            step, (ys, states, scores, done, lengths), jnp.arange(n_new)
        )
        if has_eos:
            # len**0.0 == 1.0 exactly, so applying the norm unconditionally
            # keeps alpha a dynamic scalar without perturbing alpha=0 ranks
            norm = jnp.maximum(lengths, 1).astype(jnp.float32) ** length_penalty
            best = jnp.argmax(scores / norm, axis=1)  # (B,)
        else:
            best = jnp.argmax(scores, axis=1)  # (B,)
        return ys.reshape(B, W, total)[jnp.arange(B), best]


def _names(path):
    """The dict keys along a pytree path (``""`` for a list index)."""
    return [str(getattr(k, "key", "")) for k in path]


# a Kimi Delta Attention layer's decay vectors: drawn by the operator, never decayed
_DECAY_VECTORS = ("A_log", "dt_bias")


def _is_norm(names) -> bool:
    return any(n.endswith("norm") for n in names)


class _ShortConvOperator(nn.Module):
    """Gated short convolution as a sequence operator: ``[B, C, u] =
    split3(W_in z)``, ``out = W_out (C * conv(B * u))`` with a depthwise
    causal convolution of ``taps`` positions
    (:func:`heat_tpu.ops.short_conv.gated_short_conv`); no bias anywhere."""

    def __init__(self, embed_dim: int, taps: int = 3):
        self.embed_dim, self.taps = embed_dim, taps
        self.in_proj = nn.Linear(embed_dim, 3 * embed_dim, bias=False)
        self.out_proj = nn.Linear(embed_dim, embed_dim, bias=False)

    def init(self, key):
        import jax

        k1, k2, k3 = jax.random.split(key, 3)
        bound = 1.0 / self.taps**0.5
        return {
            "in_proj": self.in_proj.init(k1),
            "conv": {"weight": jax.random.uniform(
                k2, (self.embed_dim, self.taps), minval=-bound, maxval=bound)},
            "out_proj": self.out_proj.init(k3),
        }

    def apply(self, params, x, **kw):
        import jax

        from ..ops.short_conv import gated_short_conv

        bcu = self.in_proj.apply(params["in_proj"], x)
        with jax.named_scope("ht.shortconv"):
            gated = gated_short_conv(bcu, params["conv"]["weight"])
        return self.out_proj.apply(params["out_proj"], gated)


class _PatternBlock(nn.Module):
    """``h = x + Op(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``; an expert
    FFN also returns what its routing counted (``MoE.apply_with_stats``), and
    with ``route_on_input`` its router scores ``x`` itself, not ``RMSNorm(h)``.
    With ``output_norms`` each sublayer's output goes through an RMSNorm of
    its own before the residual sum: ``h = x + RMSNorm(Op(RMSNorm(x)))``,
    ``y = h + RMSNorm(FFN(RMSNorm(h)))``.  With ``residual_scale`` each
    sublayer's output is multiplied by it ahead of the residual sum:
    ``h = x + c Op(RMSNorm(x))``, ``y = h + c FFN(RMSNorm(h))``.  An operator
    that counts what it did (sparse attention: ``apply_with_stats``) adds
    its counts to the block's stats."""

    def __init__(self, embed_dim: int, operator: nn.Module, ffn: nn.Module, eps: float,
                 route_on_input: bool = False, output_norms: bool = False, residual_scale: float = None):
        self.operator_norm = nn.RMSNorm(embed_dim, eps=eps)
        self.operator = operator
        self.ffn_norm = nn.RMSNorm(embed_dim, eps=eps)
        self.ffn = ffn
        # a norm on each sublayer's output, ahead of the residual sum
        self.operator_out_norm = nn.RMSNorm(embed_dim, eps=eps) if output_norms else None
        self.ffn_out_norm = nn.RMSNorm(embed_dim, eps=eps) if output_norms else None
        self.routed = hasattr(ffn, "apply_with_stats")
        # the router scores the block's input as it enters, ahead of the operator
        self.route_on_input = route_on_input and self.routed
        self.residual_scale = residual_scale
        self.counted = getattr(operator, "sparse", None) is not None
        # the operator's projections, beside the scope the operator gives its core
        self.scope = ("ht.shortconv.proj" if isinstance(operator, _ShortConvOperator)
                      else "ht.kda.proj" if isinstance(operator, KimiDeltaAttention) else "ht.attention.proj")

    def init(self, key):
        import jax

        k1, k2 = jax.random.split(key)
        out = {
            "operator_norm": self.operator_norm.init(None), "operator": self.operator.init(k1),
            "ffn_norm": self.ffn_norm.init(None), "ffn": self.ffn.init(k2),
        }
        if self.operator_out_norm is not None:
            out["operator_out_norm"] = self.operator_out_norm.init(None)
            out["ffn_out_norm"] = self.ffn_out_norm.init(None)
        return out

    def _normed_sum(self, params, name: str, x, out):
        """``x + RMSNorm(out)`` by the sublayer's output norm ``name``."""
        import jax

        with jax.named_scope("ht.lm.norm"):
            return x + getattr(self, name).apply(params[name], out)

    def apply(self, params, x, **kw):
        import jax

        # without output norms a residual sum stays under its sublayer's scope, where it
        # was; with them it goes with the norm, outside, so that no operation has two names
        plain = self.operator_out_norm is None
        with jax.named_scope("ht.lm.norm"):
            z = self.operator_norm.apply(params["operator_norm"], x)
        with jax.named_scope(self.scope):
            if self.counted:
                h, counts = self.operator.apply_with_stats(params["operator"], z, causal=True)
            else:
                h, counts = self.operator.apply(params["operator"], z, causal=True), None
            if self.residual_scale is not None:
                h = h * self.residual_scale
            if plain:
                h = x + h
        if not plain:
            h = self._normed_sum(params, "operator_out_norm", x, h)
        with jax.named_scope("ht.lm.norm"):
            z = self.ffn_norm.apply(params["ffn_norm"], h)
        if self.routed:
            routed_on = {"router_input": x} if self.route_on_input else {}
            out, stats = self.ffn.apply_with_stats(params["ffn"], z, **routed_on)
            if self.residual_scale is not None:
                out = out * self.residual_scale
            if counts is not None:
                stats = {**counts, **stats}
            return (h + out if plain else self._normed_sum(params, "ffn_out_norm", h, out)), stats
        with jax.named_scope("ht.mlp"):
            out = self.ffn.apply(params["ffn"], z)
            if self.residual_scale is not None:
                out = out * self.residual_scale
            if plain:
                return h + out, counts
        return self._normed_sum(params, "ffn_out_norm", h, out), counts


class PatternLM(nn.Module):
    """Pre-norm causal language model whose layers follow a pattern.

    ``layer_types[l]`` names layer ``l``'s sequence operator: ``"conv"`` (a
    gated short convolution of ``conv_taps`` positions), ``"full_attention"``
    (causal grouped-query attention with RMS-normalised query and key heads
    and rotate-half rotary positions of base ``rope_base``), ``"global_attention"``
    and ``"sliding_attention"`` (the same attention over every earlier position
    and over the nearest ``window`` alone; ``rope_kinds`` names the attention
    kinds whose queries and keys are rotated, the others see no positions at
    all; ``qk_norm=False`` leaves every attention kind's heads unnormalised,
    ``head_dim`` gives them a width that is not ``embed_dim / num_heads``), ``"kda"`` (Kimi
    Delta Attention, ``kda_heads`` heads of ``kda_head_dim`` with a
    convolution of ``conv_taps`` positions, low-rank gates of ``kda_gate_rank``
    and chunks of ``kda_chunk`` tokens: :class:`~heat_tpu.nn.KimiDeltaAttention`)
    or ``"mla"`` (latent attention, ``num_heads`` heads of ``qk_nope_dim +
    qk_shared_dim`` on a latent of ``kv_rank`` normalised with
    ``kv_norm_eps``, by default ``norm_eps``, with values of ``v_dim``:
    :class:`~heat_tpu.nn.LatentAttention`; without positions unless
    ``rope_kinds`` names ``"mla"``, and then its shared key part and the
    matching part of every query head are rotated with base ``rope_base``),
    ``"sparse_attention"`` (the grouped-query attention of the kinds above
    over the key blocks each token selects, ``sparse`` an
    :class:`~heat_tpu.ops.sparse_attention.SparseConfig`:
    ``MultiheadAttention(sparse=)``; its block's stats hold the pairs it kept)
    or ``"lightning"`` (linear attention with a constant decay a head,
    ``lightning_heads`` heads of ``lightning_head_dim``, chunks of
    ``lightning_chunk`` tokens, the decays of the layer's index among
    ``published_layers`` (absent: the layers here), rotated where
    ``rope_kinds`` names it, QK-normed with ``qk_norm``:
    :class:`~heat_tpu.nn.LightningAttention`).
    The first ``num_dense_layers`` layers have a SwiGLU
    feed-forward of width ``ffn_dim``; with ``num_experts`` set, every later
    layer has ``num_experts`` SwiGLU experts of width ``expert_dim``,
    ``experts_per_token`` of them a token, chosen by sigmoid scores plus a
    selection bias (a buffer) and weighted by the renormalised scores (the
    chosen scores over their sum ``+ 1e-6``, ``MoE._route``'s constant: a
    published model's own may differ, ``1e-20`` in one, by less than float32
    resolves), routed without drops (``MoE(dispatch="sorted")``).  ``router_scoring="softmax"``
    chooses by the logits alone and weights by a softmax over the chosen (no
    selection bias), ``expert_activation="relu"`` makes the experts ReGLU, and
    ``route_before_operator`` has each layer's router score the layer's input
    as it enters, ahead of the norm and the sequence operator.  ``experts_held`` (a
    ``range``) says which experts' weights live on this rank: the router
    still scores all of them, and what the absent ones would add is left
    out.  ``shared_expert_dim`` adds a SwiGLU of that width that every token
    of an expert layer goes through, and ``expert_rows_bound`` sizes the
    expert layers' buffers (``MoE(shared_dim=, rows_bound=)``).  RMSNorm
    everywhere, no bias anywhere; the token embedding is also the output
    head unless ``tie_embedding=False`` gives the head a matrix of its own.
    ``attention_gate`` gives every attention kind's layers a gate on their
    merged heads (``MultiheadAttention(gate=True)``), ``output_norms`` every
    block an RMSNorm on each sublayer's output ahead of the residual sum as
    well as the one on its input, and ``embedding_scale`` multiplies the
    embedded tokens as they enter the first layer (``sqrt(embed_dim)`` in
    models that scale it so).  ``residual_scale`` multiplies every
    sublayer's output ahead of its residual sum, ``head_input_scale`` the
    final normalised states ahead of the output head.

    A layer may hold its tensor-parallel share of a wider one (ranges, as
    ``experts_held`` is): ``heads_held`` of the ``num_heads`` query heads and
    ``kv_heads_held`` of the ``num_kv_heads`` (whole groups),
    ``lightning_heads_held`` of the ``lightning_heads``, ``ffn_held`` of the
    ``ffn_dim`` columns of a dense feed-forward.  The layer computes its share
    alone, and its output projection gives the partial sum that the other
    shares' all-reduce would complete.

    Parameters are float32, drawn ``N(0, init_std)`` (norm weights 1, the
    selection bias ``N(0, bias_std)`` and then fixed, a ``"kda"`` layer's
    ``A_log = log U(1, 16)`` and ``dt_bias = softplus^-1(dt)`` with ``log dt
    ~ U(log 0.001, log 0.1)``).  ``dtype`` is the
    dtype of the activations and of the operands of the matrix products
    (``None``: the parameters'); norms' statistics, routing scores, softmax
    and loss stay float32.  Every block is rematerialised under ``grad``.

    ``apply(params, tokens)`` with tokens ``(B, S)`` returns ``(logits
    (B, S, vocab) in dtype, stats)``; ``stats`` holds, per expert layer, the
    rows routed to each expert held and the rows dropped (always 0 on this
    path), for the ``stats=`` hook of ``DataParallel.make_train_step``.
    ``next_token_loss(params, tokens)`` returns ``(the mean next-token
    cross-entropy, stats)`` without ever holding the logits: the final norm,
    the head's product and the loss a block of rows at a time
    (``make_train_step(..., forward=model.next_token_loss)``).
    ``decay_mask(params)`` is the usual weight-decay mask (matrices, an
    untied head among them, yes; norms, selection bias, embedding, ``A_log``
    and ``dt_bias`` no).
    """

    def __init__(self, vocab_size: int, embed_dim: int, layer_types: Sequence[str], *,
                 num_heads: int, num_kv_heads: int = None, ffn_dim: int,
                 num_dense_layers: int = None, num_experts: int = None,
                 experts_per_token: int = 2, expert_dim: int = None, experts_held=None,
                 routed_scaling: float = 1.0, norm_topk: bool = True,
                 conv_taps: int = 3, rope_base: float = 1e6, norm_eps: float = 1e-5,
                 init_std: float = 0.02, bias_std: float = 0.0, dtype=None,
                 tie_embedding: bool = True, shared_expert_dim: int = None,
                 expert_rows_bound: int = None, kda_heads: int = None, kda_head_dim: int = None,
                 kda_gate_rank: int = None, kda_chunk: int = 64, kv_rank: int = None,
                 qk_nope_dim: int = None, qk_shared_dim: int = None, v_dim: int = None,
                 head_dim: int = None, qk_norm: bool = True, window: int = None,
                 rope_kinds: Sequence[str] = ("full_attention", "sliding_attention"),
                 router_scoring: str = "sigmoid", expert_activation: str = "silu",
                 route_before_operator: bool = False, attention_gate: bool = False,
                 output_norms: bool = False, embedding_scale: float = None, kv_norm_eps: float = None,
                 sparse=None, lightning_heads: int = None, lightning_head_dim: int = None,
                 lightning_chunk: int = 128, published_layers: int = None, heads_held=None,
                 kv_heads_held=None, lightning_heads_held=None, ffn_held=None,
                 residual_scale: float = None, head_input_scale: float = None):
        from .attention import LatentAttention, MultiheadAttention
        from .moe import MoE

        attention_kinds = ("full_attention", "global_attention", "sliding_attention", "sparse_attention")
        unknown = sorted(set(layer_types) - {"conv", "kda", "mla", "lightning", *attention_kinds})
        if unknown:
            raise ValueError(f"layer_types may hold 'conv', 'lightning', 'kda' and 'mla' or an attention "
                             f"kind {attention_kinds}, got {unknown}")
        if "sliding_attention" in layer_types and window is None:
            raise ValueError("a 'sliding_attention' layer needs window=")
        if "sparse_attention" in layer_types and sparse is None:
            raise ValueError("a 'sparse_attention' layer needs sparse=")
        n_heads, n_kv_heads = num_heads, num_kv_heads
        if heads_held is not None or kv_heads_held is not None:
            kv_total = num_heads if num_kv_heads is None else num_kv_heads
            heads_held = range(num_heads) if heads_held is None else heads_held
            kv_heads_held = range(kv_total) if kv_heads_held is None else kv_heads_held
            group = num_heads // kv_total
            if (kv_heads_held.stop > kv_total or kv_heads_held.step != 1
                    or heads_held != range(kv_heads_held.start * group, kv_heads_held.stop * group)):
                raise ValueError("heads_held must be the query heads of the groups kv_heads_held names")
            n_heads, n_kv_heads = len(heads_held), len(kv_heads_held)
        l_held = range(lightning_heads or 0) if lightning_heads_held is None else lightning_heads_held
        ffn_width = ffn_dim if ffn_held is None else len(ffn_held)

        def attention(kind):
            return lambda: MultiheadAttention(
                embed_dim, n_heads, bias=False, rope=kind in rope_kinds, rope_base=rope_base,
                rope_pairing="half", num_kv_heads=n_kv_heads, qk_norm=qk_norm, qk_norm_eps=norm_eps,
                head_dim=head_dim, window=window if kind == "sliding_attention" else None,
                gate=attention_gate, sparse=sparse if kind == "sparse_attention" else None)

        def lightning(layer):
            return LightningAttention(
                embed_dim, len(l_held), lightning_head_dim, layer=layer,
                layers=published_layers or len(layer_types), total_heads=lightning_heads,
                head_offset=l_held.start, qk_norm=qk_norm, rope="lightning" in rope_kinds,
                rope_base=rope_base, eps=norm_eps, chunk=lightning_chunk)

        n_dense = len(layer_types) if num_dense_layers is None or not num_experts else num_dense_layers
        self.vocab_size, self.embed_dim = vocab_size, embed_dim
        self.layer_types = tuple(layer_types)
        self.init_std, self.bias_std, self.dtype = init_std, bias_std, dtype
        self.embedding_scale, self.head_input_scale = embedding_scale, head_input_scale
        self.embed = nn.Embedding(vocab_size, embed_dim)
        self.head = None if tie_embedding else nn.Linear(embed_dim, vocab_size, bias=False)
        operators = {
            "conv": lambda: _ShortConvOperator(embed_dim, conv_taps),
            **{kind: attention(kind) for kind in attention_kinds},
            "kda": lambda: KimiDeltaAttention(
                embed_dim, kda_heads, kda_head_dim, conv_taps=conv_taps, gate_rank=kda_gate_rank,
                chunk=kda_chunk, eps=norm_eps),
            "mla": lambda: LatentAttention(
                embed_dim, num_heads, kv_rank=kv_rank, qk_nope_dim=qk_nope_dim,
                qk_shared_dim=qk_shared_dim, v_dim=v_dim, eps=norm_eps if kv_norm_eps is None else kv_norm_eps,
                rope="mla" in rope_kinds, rope_base=rope_base),
        }
        self.blocks = []
        for i, kind in enumerate(self.layer_types):
            ffn = nn.SwiGLU(embed_dim, ffn_width) if i < n_dense else MoE(
                embed_dim, num_experts, hidden_dim=expert_dim, top_k=experts_per_token,
                gated=True, scoring=router_scoring, expert_bias=router_scoring == "sigmoid",
                norm_topk=norm_topk, routed_scaling=routed_scaling, dispatch="sorted",
                experts_held=experts_held, shared_dim=shared_expert_dim, rows_bound=expert_rows_bound,
                activation=expert_activation)
            operator = lightning(i) if kind == "lightning" else operators[kind]()
            self.blocks.append(_PatternBlock(embed_dim, operator, ffn, norm_eps,
                                             route_on_input=route_before_operator,
                                             output_norms=output_norms, residual_scale=residual_scale))
        self.norm = nn.RMSNorm(embed_dim, eps=norm_eps)
        self._remat_fns = [{} for _ in self.blocks]

    def _structure(self, key):
        out = {"embed": self.embed.init(key), "blocks": [b.init(key) for b in self.blocks],
               "norm": self.norm.init(None)}
        if self.head is not None:
            out["head"] = self.head.init(key)
        return out

    def init(self, key):
        """Every matrix ``N(0, init_std)``, every norm weight 1, the selection
        bias ``N(0, bias_std)``, ``A_log`` and ``dt_bias`` as their operator
        draws them: one draw per leaf, keyed by its place."""
        import jax
        import jax.numpy as jnp

        shapes = jax.eval_shape(self._structure, key)
        flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

        def draw(i, path, leaf):
            names = _names(path)
            if _is_norm(names):
                return jnp.ones(leaf.shape, jnp.float32)
            if names[-1] in _DECAY_VECTORS:  # the operator's own draw, under this leaf's key
                drawn = self.blocks[path[1].idx].operator.init_decay(jax.random.fold_in(key, i))
                return drawn[names[-1]].astype(jnp.float32)
            std = self.bias_std if "expert_bias" in names else self.init_std
            return std * jax.random.normal(jax.random.fold_in(key, i), leaf.shape, jnp.float32)

        return jax.tree_util.tree_unflatten(
            treedef, [draw(i, path, leaf) for i, (path, leaf) in enumerate(flat)])

    def decay_mask(self, params):
        import jax

        def decays(path, _):
            names = _names(path)
            return not ("embed" in names or "expert_bias" in names or _is_norm(names)
                        or names[-1] in _DECAY_VECTORS)

        return jax.tree_util.tree_map_with_path(decays, params)

    def _cast(self, tree):
        """The matrices in the activations' dtype; vectors (norm weights,
        the selection bias, ``A_log``, ``dt_bias``) and the router stay
        float32."""
        import jax

        if self.dtype is None:
            return tree

        def cast(path, a):
            return a if a.ndim < 2 or "router" in _names(path) else a.astype(self.dtype)

        return jax.tree_util.tree_map_with_path(cast, tree)

    def _states(self, params, tokens, train: bool):
        """``(the last block's output (B, S, D), the output head's matrix in
        the activations' dtype, stats)``: everything ahead of the final norm."""
        import jax

        with jax.named_scope("ht.lm.cast"):
            embedding = self._cast(params["embed"])["weight"]  # also the output head, if tied
            head = embedding if self.head is None else self._cast(params["head"])["weight"]
        with jax.named_scope("ht.lm.embed"):
            h = embedding[tokens]
            if self.embedding_scale is not None:
                h = h * self.embedding_scale
        stats = []
        for block, cache, p in zip(self.blocks, self._remat_fns, params["blocks"]):
            def run(p, h, block=block):
                with jax.named_scope("ht.lm.cast"):
                    p = self._cast(p)
                return block.apply(p, h)

            with jax.named_scope("ht.lm.block"):
                h, s = _remat_jit(cache, train, run)(p, h)
            if s is not None:
                stats.append(s)
        return h, head, stats

    def apply(self, params, tokens, *, train: bool = False, key=None):
        import jax

        h, head, stats = self._states(params, tokens, train)
        with jax.named_scope("ht.lm.head_loss"):
            logits = self._head_input(params, h) @ head.T
        return logits, stats

    def _head_input(self, params, h):
        """The final norm, times ``head_input_scale`` where there is one."""
        h = self.norm.apply(params["norm"], h)
        return h if self.head_input_scale is None else h * self.head_input_scale

    def next_token_loss(self, params, tokens, *, train: bool = False, key=None, block_rows: int = 8192):
        """``(mean next-token cross-entropy of tokens (B, S), stats)``: what
        :func:`~heat_tpu.nn.losses.next_token_cross_entropy` makes of
        ``apply``'s logits, by
        :func:`~heat_tpu.nn.losses.next_token_cross_entropy_by_rows`: the final
        norm, the head's product and the loss ``block_rows`` rows at a time, no
        ``(B, S, vocab)`` array.  ``DataParallel.make_train_step(...,
        forward=model.next_token_loss)`` trains on it."""
        from .losses import next_token_cross_entropy_by_rows

        h, head, stats = self._states(params, tokens, train)
        loss = next_token_cross_entropy_by_rows(
            h, head, tokens, norm=lambda rows: self._head_input(params, rows), block_rows=block_rows)
        return loss, stats
