"""Loss modules mirroring ``torch.nn``'s criterion classes.

The reference inherits these from ``torch.nn`` wholesale (SURVEY §2.5);
here each is a thin parameter-free :class:`~heat_tpu.nn.modules.Module`
over the corresponding ``ht.nn.functional`` form, so the same object works
as ``loss(params, pred, target)`` free function or inside a training step.
Verified against the ``torch.nn`` oracle in ``tests/test_nn_activations.py``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import optax

from .modules import Module
from .spatial import CosineSimilarity, PairwiseDistance
from . import functional as F

__all__ = [
    "BCELoss", "BCEWithLogitsLoss", "CTCLoss", "CosineEmbeddingLoss",
    "CrossEntropyLoss", "GaussianNLLLoss", "HingeEmbeddingLoss", "HuberLoss",
    "KLDivLoss", "L1Loss", "MSELoss", "MarginRankingLoss",
    "MultiLabelMarginLoss", "MultiLabelSoftMarginLoss", "MultiMarginLoss", "NLLLoss",
    "PoissonNLLLoss", "SmoothL1Loss", "SoftMarginLoss", "TripletMarginLoss",
    "TripletMarginWithDistanceLoss", "next_token_cross_entropy",
    "next_token_cross_entropy_by_rows",
]


class _Loss(Module):
    """Criterion base: ``reduction`` in {'mean', 'sum', 'none'} (torch
    default 'mean'); ``apply(params, *inputs)`` — params unused, kept for
    the Module calling convention.  ``_arity`` is the criterion's tensor
    count (2 for pred/target; ranking/triplet losses take 3)."""

    _reductions = ("mean", "sum", "none")
    _arity = 2

    def __init__(self, reduction: str = "mean"):
        if reduction not in self._reductions:
            raise ValueError(f"unknown reduction {reduction!r}")
        self.reduction = reduction

    def _fn(self, *inputs):
        raise NotImplementedError

    def apply(self, params, *inputs, target=None, **kw):
        if target is not None:
            inputs = inputs + (target,)
        return self._fn(*inputs)

    def __call__(self, *args, **kw):
        # criterion convenience: loss(pred, target, ...) without params, the
        # torch call shape — or the full Module form loss(params, pred, ...).
        # A target= kwarg disambiguates loss(params, pred, target=t), which
        # also has _arity positionals but must route through apply
        if len(args) == self._arity and "target" not in kw:
            return self._fn(*args)
        return self.apply(*args, **kw)


class MSELoss(_Loss):
    def _fn(self, pred, target):
        return F.mse_loss(pred, target, reduction=self.reduction)


class L1Loss(_Loss):
    def _fn(self, pred, target):
        return F.l1_loss(pred, target, reduction=self.reduction)


class CrossEntropyLoss(_Loss):
    def _fn(self, pred, target):
        return F.cross_entropy(pred, target, reduction=self.reduction)


class NLLLoss(_Loss):
    def _fn(self, pred, target):
        return F.nll_loss(pred, target, reduction=self.reduction)


class BCELoss(_Loss):
    def _fn(self, pred, target):
        return F.binary_cross_entropy(pred, target, reduction=self.reduction)


class BCEWithLogitsLoss(_Loss):
    def _fn(self, pred, target):
        return F.binary_cross_entropy_with_logits(pred, target, reduction=self.reduction)


class HuberLoss(_Loss):
    def __init__(self, reduction: str = "mean", delta: float = 1.0):
        super().__init__(reduction)
        self.delta = delta

    def _fn(self, pred, target):
        return F.huber_loss(pred, target, reduction=self.reduction, delta=self.delta)


class SmoothL1Loss(_Loss):
    def __init__(self, reduction: str = "mean", beta: float = 1.0):
        super().__init__(reduction)
        self.beta = beta

    def _fn(self, pred, target):
        return F.smooth_l1_loss(pred, target, reduction=self.reduction, beta=self.beta)


class SoftMarginLoss(_Loss):
    """log(1 + exp(-y·x)) with targets in {-1, +1}."""

    def _fn(self, pred, target):
        v = jax.nn.softplus(-F._j(target) * F._j(pred))
        return F._reduce(v, self.reduction)


class HingeEmbeddingLoss(_Loss):
    """x where y == 1, max(0, margin - x) where y == -1."""

    def __init__(self, margin: float = 1.0, reduction: str = "mean"):
        super().__init__(reduction)
        self.margin = margin

    def _fn(self, pred, target):
        x, y = F._j(pred), F._j(target)
        v = jnp.where(y == 1, x, jnp.maximum(0.0, self.margin - x))
        return F._reduce(v, self.reduction)


class MarginRankingLoss(_Loss):
    """max(0, -y·(x1 - x2) + margin) — y = +1 ranks x1 above x2."""

    _arity = 3

    def __init__(self, margin: float = 0.0, reduction: str = "mean"):
        super().__init__(reduction)
        self.margin = margin

    def _fn(self, x1, x2, target):
        v = jnp.maximum(0.0, -F._j(target) * (F._j(x1) - F._j(x2)) + self.margin)
        return F._reduce(v, self.reduction)


class CosineEmbeddingLoss(_Loss):
    """1 - cos(x1, x2) for y == 1; max(0, cos(x1, x2) - margin) for y == -1
    (cosine along dim 1, torch's eps-clamped norms)."""

    _arity = 3

    def __init__(self, margin: float = 0.0, reduction: str = "mean"):
        super().__init__(reduction)
        self.margin = margin

    def _fn(self, x1, x2, target):
        a, b, y = F._j(x1), F._j(x2), F._j(target)
        # torch accepts (N, D) or unbatched (D,): feature axis is the last
        cos = CosineSimilarity(dim=a.ndim - 1)(a, b)
        v = jnp.where(y == 1, 1.0 - cos, jnp.maximum(0.0, cos - self.margin))
        return F._reduce(v, self.reduction)


class GaussianNLLLoss(_Loss):
    """0.5·(log max(var, eps) + (x - t)² / max(var, eps)) [+ 0.5·log 2π]
    — torch call shape ``loss(input, target, var)``."""

    _arity = 3

    def __init__(self, full: bool = False, eps: float = 1e-6,
                 reduction: str = "mean"):
        super().__init__(reduction)
        self.full = full
        self.eps = eps

    def _fn(self, pred, target, var):
        v = jnp.maximum(F._j(var), self.eps)
        out = 0.5 * (jnp.log(v) + (F._j(pred) - F._j(target)) ** 2 / v)
        if self.full:
            out = out + 0.5 * math.log(2 * math.pi)
        return F._reduce(out, self.reduction)


class PoissonNLLLoss(_Loss):
    """exp(x) - t·x (log-space input, the default) or x - t·log(x + eps);
    ``full`` adds the Stirling approximation for t > 1 (torch formula)."""

    def __init__(self, log_input: bool = True, full: bool = False,
                 eps: float = 1e-8, reduction: str = "mean"):
        super().__init__(reduction)
        self.log_input = log_input
        self.full = full
        self.eps = eps

    def _fn(self, pred, target):
        x, t = F._j(pred), F._j(target)
        if self.log_input:
            v = jnp.exp(x) - t * x
        else:
            v = x - t * jnp.log(x + self.eps)
        if self.full:
            stirling = t * jnp.log(jnp.where(t > 1, t, 1.0)) - t + 0.5 * jnp.log(
                2 * math.pi * jnp.where(t > 1, t, 1.0)
            )
            v = v + jnp.where(t > 1, stirling, 0.0)
        return F._reduce(v, self.reduction)


class TripletMarginLoss(_Loss):
    """max(0, d(a, p) - d(a, n) + margin) with the torch pairwise p-norm
    (additive eps); ``swap`` uses min(d(a, n), d(p, n)) as the negative
    distance."""

    _arity = 3

    def __init__(self, margin: float = 1.0, p: float = 2.0, eps: float = 1e-6,
                 swap: bool = False, reduction: str = "mean"):
        super().__init__(reduction)
        self.margin = margin
        self.p = p
        self.eps = eps
        self.swap = swap

    def _fn(self, anchor, positive, negative):
        # one implementation of the triplet rule: the callable-distance
        # variant, specialized with the torch pairwise p-norm
        return TripletMarginWithDistanceLoss(
            distance_function=PairwiseDistance(p=self.p, eps=self.eps),
            margin=self.margin, swap=self.swap, reduction=self.reduction,
        )._fn(anchor, positive, negative)


class KLDivLoss(_Loss):
    _reductions = ("mean", "sum", "none", "batchmean")  # torch: KL only

    def __init__(self, reduction: str = "mean", log_target: bool = False):
        super().__init__(reduction)
        self.log_target = log_target

    def _fn(self, pred, target):
        return F.kl_div(pred, target, reduction=self.reduction, log_target=self.log_target)


class MultiLabelSoftMarginLoss(_Loss):
    """Per-class binary logistic loss averaged over classes (torch formula):
    ``-1/C · Σ_c [y·logσ(x) + (1-y)·logσ(-x)]``."""

    def _fn(self, pred, target):
        x, y = F._j(pred), F._j(target)
        v = -(y * jax.nn.log_sigmoid(x) + (1.0 - y) * jax.nn.log_sigmoid(-x))
        return F._reduce(v.mean(axis=-1), self.reduction)


class MultiMarginLoss(_Loss):
    """Multi-class hinge (torch formula): ``1/C · Σ_{i≠y} max(0, margin -
    x[y] + x[i])^p`` with integer class targets."""

    def __init__(self, p: int = 1, margin: float = 1.0, reduction: str = "mean"):
        if p not in (1, 2):
            raise ValueError(f"p must be 1 or 2, got {p}")
        super().__init__(reduction)
        self.p = p
        self.margin = margin

    def _fn(self, pred, target):
        x = F._j(pred)
        y = F._j(target).astype(jnp.int32)
        C = x.shape[-1]
        xy = jnp.take_along_axis(x, y[..., None], axis=-1)
        h = jnp.maximum(0.0, self.margin - xy + x) ** self.p
        # the i == y term contributes max(0, margin)^p; torch excludes it
        h = h * (jnp.arange(C) != y[..., None])
        return F._reduce(h.sum(axis=-1) / C, self.reduction)


class CTCLoss(_Loss):
    """Connectionist temporal classification, torch call shape:
    ``ctc(log_probs (T, N, C), targets (N, S), input_lengths (N),
    target_lengths (N))`` — delegated to ``optax.ctc_loss`` (the JAX-native
    forward-backward), with the layout/padding conversion here.  Targets
    must be the padded 2-D form (the reference's torch backend also
    accepts a concatenated 1-D form; pad with any value, e.g. 0).
    ``reduction='mean'`` divides each sequence loss by its target length,
    then averages (torch semantics)."""

    def __init__(self, blank: int = 0, reduction: str = "mean",
                 zero_infinity: bool = False):
        super().__init__(reduction)
        self.blank = blank
        self.zero_infinity = zero_infinity

    def _fn(self, log_probs, targets, input_lengths, target_lengths):
        lp = F._j(log_probs)
        tg = F._j(targets).astype(jnp.int32)
        il = F._j(input_lengths).astype(jnp.int32)
        tl = F._j(target_lengths).astype(jnp.int32)
        if tg.ndim != 2:
            raise ValueError(
                "CTCLoss expects padded 2-D targets (N, S); the concatenated "
                "1-D torch form is not supported — reshape with per-sequence "
                "rows")
        T = lp.shape[0]
        S = tg.shape[1]
        logits = jnp.swapaxes(lp, 0, 1)  # (N, T, C), optax layout
        logit_pad = (jnp.arange(T)[None, :] >= il[:, None]).astype(lp.dtype)
        label_pad = (jnp.arange(S)[None, :] >= tl[:, None]).astype(lp.dtype)
        per_seq = optax.ctc_loss(logits, logit_pad, tg, label_pad,
                                 blank_id=self.blank)
        # optax clamps log(0) to a large finite value, so infeasible
        # alignments never read as inf — detect them explicitly: a CTC path
        # needs target_length + (adjacent repeats, which force a blank)
        # frames.  torch returns inf there (zeroed under zero_infinity)
        valid = jnp.arange(S)[None, :] < tl[:, None]
        rep = jnp.zeros_like(tl) if S < 2 else (
            (tg[:, 1:] == tg[:, :-1]) & valid[:, 1:]
        ).sum(axis=1)
        infeasible = tl + rep > il
        per_seq = jnp.where(infeasible, jnp.inf, per_seq)
        if self.zero_infinity:
            per_seq = jnp.where(jnp.isfinite(per_seq), per_seq, 0.0)
        if self.reduction == "mean":
            # torch: per-sequence loss / target_length, then batch mean
            return jnp.mean(per_seq / jnp.maximum(tl, 1))
        return F._reduce(per_seq, self.reduction)

    _arity = 4


class TripletMarginWithDistanceLoss(_Loss):
    """TripletMarginLoss with a caller-supplied distance callable
    (default: the torch pairwise Euclidean distance)."""

    _arity = 3

    def __init__(self, distance_function=None, margin: float = 1.0,
                 swap: bool = False, reduction: str = "mean"):
        super().__init__(reduction)
        self.distance_function = (
            distance_function if distance_function is not None
            else PairwiseDistance()
        )
        self.margin = margin
        self.swap = swap

    def _fn(self, anchor, positive, negative):
        d = self.distance_function
        a, p_, n = F._j(anchor), F._j(positive), F._j(negative)
        d_pos = d(a, p_)
        d_neg = d(a, n)
        if self.swap:
            d_neg = jnp.minimum(d_neg, d(p_, n))
        v = jnp.maximum(0.0, d_pos - d_neg + self.margin)
        return F._reduce(v, self.reduction)


class MultiLabelMarginLoss(_Loss):
    """Label-SET margin (torch formula): for each sample,
    ``Σ_{j∈targets} Σ_{i∉targets} max(0, 1 - (x[y_j] - x[i])) / C`` where
    the target row lists class indices and the first -1 terminates it."""

    def _fn(self, pred, target):
        x = F._j(pred)
        y = F._j(target).astype(jnp.int32)
        if x.ndim == 1:
            x, y = x[None], y[None]
            squeeze = True
        else:
            squeeze = False
        C = x.shape[-1]
        # valid targets: before the first -1 (torch contract)
        first_neg = jnp.cumsum(y < 0, axis=-1) > 0
        valid = ~first_neg
        y_safe = jnp.where(valid, y, 0)
        # membership mask: class c is in the sample's target set
        member = jnp.zeros(x.shape, bool)
        member = member.at[
            jnp.arange(x.shape[0])[:, None], y_safe
        ].max(valid)
        xy = jnp.take_along_axis(x, y_safe, axis=-1)  # (N, T) target scores
        # hinge for every (target j, class i) pair, masked to j valid, i not
        # in the target set
        h = jnp.maximum(0.0, 1.0 - (xy[:, :, None] - x[:, None, :]))
        mask = valid[:, :, None] & ~member[:, None, :]
        v = (h * mask).sum(axis=(1, 2)) / C
        if squeeze:
            v = v[0]
        return F._reduce(v, self.reduction)


def next_token_cross_entropy(logits, tokens):
    """Mean cross-entropy of ``logits[:, t]`` against ``tokens[:, t + 1]``
    over all sequences and all positions but the last: a causal language
    model's training loss.  ``logits`` is ``(B, S, V)`` in any float dtype,
    ``tokens`` ``(B, S)`` integers.

    The log-sum-exp is float32, one sequence at a time and rematerialised
    under ``grad``, so ``B x S x V`` logits kept in bfloat16 are never held in
    float32 all at once: at 32,768 positions of a 16,384-word vocabulary that
    is 0.5 GB a sequence in place of 2 GB.  With one sequence that saves
    nothing (the one float32 block is the whole batch's), and the logits and
    their cotangent are held whole whatever the batch:
    :func:`next_token_cross_entropy_by_rows` takes the head's product into the
    blocks, so that no ``(B, S, V)`` array exists."""

    @jax.checkpoint
    def sequence(args):
        lg, targets = args
        lg = lg[:-1].astype(jnp.float32)
        picked = jnp.take_along_axis(lg, targets[1:, None], axis=-1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(lg, axis=-1) - picked)

    with jax.named_scope("ht.lm.head_loss"):
        total = jnp.sum(jax.lax.map(sequence, (logits, tokens)))
        return total / (tokens.shape[0] * (tokens.shape[1] - 1))


def next_token_cross_entropy_by_rows(states, head, tokens, *, norm=None, block_rows: int = 8192):
    """:func:`next_token_cross_entropy` of ``logits = norm(states) head^T``
    without the logits: the final norm, the head's product, the float32
    log-sum-exp and the picked logit are computed ``block_rows`` rows of the
    flattened ``(B x S, D)`` states at a time, each block rematerialised under
    ``grad`` (the backward pass makes a block's logits again), so the largest
    array is one block's ``(block_rows, V)`` and neither the ``(B, S, V)``
    logits nor their cotangent exist.  ``states`` is ``(B, S, D)``, ``head``
    ``(V, D)`` in the dtype of the product's operands, ``tokens`` ``(B, S)``
    integers; ``norm`` (rows in, rows out: the model's final norm) is applied
    to a block's rows, ``None`` where ``states`` are normalised already.  The
    last block is padded with rows that count for nothing where ``B x S`` is
    no multiple of ``block_rows``; one block of ``B x S`` rows where that is
    smaller."""
    n, length, d = states.shape
    rows = n * length
    # a sequence's last position predicts nothing: its row counts 0, under any target
    targets = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1).reshape(rows)
    counts = jnp.broadcast_to(jnp.arange(length) < length - 1, (n, length)).reshape(rows)
    block = min(block_rows, rows)
    pad = -rows % block
    blocks = lambda a: jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1)).reshape(  # noqa: E731
        (-1, block) + a.shape[1:])

    @jax.checkpoint
    def one(args):
        h, target, count = args
        if norm is not None:
            h = norm(h)
        lg = (h @ head.T).astype(jnp.float32)
        picked = jnp.take_along_axis(lg, target[:, None], axis=-1)[:, 0]
        return jnp.sum(jnp.where(count, jax.nn.logsumexp(lg, axis=-1) - picked, 0.0))

    with jax.named_scope("ht.lm.head_loss"):
        total = jnp.sum(jax.lax.map(one, (blocks(states.reshape(rows, d)), blocks(targets), blocks(counts))))
        return total / (n * (length - 1))
