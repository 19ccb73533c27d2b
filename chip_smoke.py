"""chip_smoke.py: the quickest proof that the system still starts on the chip.

One process drives the three main paths through their ordinary entry points
over the default mesh of every attached chip: the ``ht.*`` array path at
BASELINE widths, the two trainers (``DataParallel`` MLP, ``DASO`` ResNet-50),
and the model layer with the Pallas kernels engaged (``TransformerLM``, the
flash-attention family against the dense reference, ``chunk_kda``'s and
``conv_silu_heads``' kernels against their XLA forms); on more than one chip
also the ring, expert-parallel, pipeline and two-tier DASO paths.  Every
phase checks its result (shape, finiteness, agreement with a reference,
placement on every chip) and the first fault raises: there is no ``try``
that continues, no fallback to the CPU and no subprocess.

``python chip_smoke.py`` demands the TPU: ``jax_platforms`` is pinned to
``tpu`` before first device use, so without a chip jax raises, nothing is
printed on stdout and the exit code is non-zero.  Each phase prints one line
whose seconds INCLUDE compilation: they say where a cold start spends its
time and are not device metrics.  The last stdout line is one JSON object
naming the device as jax reports it.

The phases are functions of their sizes: ``tests/test_chip_smoke.py`` runs
them at toy sizes on the 8-device CPU mesh (kernels in interpret mode).
"""

from __future__ import annotations

import gc
import importlib
import json
import math
import time

import jax

if __name__ == "__main__":
    # A missing chip is an error here, not jax's quiet fall to the CPU.
    # Pinned before heat_tpu is imported: its import already touches the
    # backend, and the pin has to come before first device use.
    jax.config.update("jax_platforms", "tpu")

import jax.numpy as jnp
import numpy as np

import heat_tpu as ht
from heat_tpu.ops.flash_attention import (
    _dense_attention, flash_attention, flash_attention_block,
    flash_attention_gqa, path_counts,
)
from heat_tpu.ops import kda, short_conv
from heat_tpu.parallel.ring_attention import (
    _block_impl, path_counts as ring_counts, ring_attention,
)

# BASELINE.json's own widths (configs 0-4) and the model-layer sizes
FULL = dict(
    matmul_n=16384,
    resplit_n=16384,
    qr_shape=(1_000_000, 256),
    kmeans=(2**23, 32, 64),
    fft_n=2**20,
    mlp_batch_per_chip=256,
    daso_model=lambda: ht.nn.models.resnet50(),
    daso_image=(3, 224, 224), daso_classes=1000, daso_batch_per_chip=32,
    lm=dict(vocab_size=32768, embed_dim=512, num_heads=8, depth=8, max_len=1024),
    lm_batch=8, lm_seq=1024, lm_prompt=64, lm_new=64,
    attn=(4, 8, 4096, 64), attn_kv_heads=2, attn_long=(2, 8, 32768, 64),
    kda=(32, 8192, 128),  # one sequence of the cell kimi_linear_48b_a3b_train_2x8k
    kda_conv=(2, 8192, 32, 128),  # a layer of that cell: sequences a chip, tokens, heads, their width
    # a windowed layer of the cell trinity_mini_26b_a3b_train_1x32k, a sequence a chip
    gated_window=dict(embed=2048, heads=32, kv_heads=4, head_dim=128, seq=32768, window=2048, prefix=512),
    ring=(2, 8, 4096, 64),  # S is per chip
    moe=dict(embed=1024, hidden=4096, experts_per_chip=8, tokens_per_chip=512),
    pipe=dict(embed=512, heads=8, seq=1024, batch_per_chip=2),
)


# ---------------------------------------------------------------------- #
# reporting and placement checks
# ---------------------------------------------------------------------- #
def _device() -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(jax.devices())}


def _done(name: str, t0: float, **facts) -> None:
    dev = _device()
    tail = " ".join(f"{k}={v}" for k, v in facts.items())
    print(
        f"PHASE {name} ok wall_s_incl_compile={time.perf_counter() - t0:.1f} "
        f"platform={dev['platform']} device_kind={dev['kind']!r} "
        f"devices={dev['count']} {tail}".rstrip(),
        flush=True,
    )


def _spans_all(x, what: str) -> None:
    """The array (or every leaf of the tree) lives on every attached chip:
    code that has only met virtual devices may put everything on the first."""
    for leaf in jax.tree.leaves(x):
        arr = getattr(leaf, "_parray", leaf)
        have = arr.sharding.device_set
        assert have == set(jax.devices()), (
            f"{what}: on {len(have)} of {len(jax.devices())} devices")


def _all_chips_hold_bytes(what: str) -> None:
    for d in jax.devices():
        stats = d.memory_stats()  # None on the CPU backend
        if stats is not None:
            assert stats["bytes_in_use"] > 0, f"{what}: {d} holds no bytes"


def _finite(x) -> bool:
    return bool(jnp.isfinite(jnp.asarray(x, jnp.float32)).all())


def _rel_err(a, b) -> float:
    a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    return float(jnp.max(jnp.abs(a - b)) / jnp.maximum(jnp.max(jnp.abs(b)), 1e-30))


# bf16 carries 8 significant bits: two results that each round once (and
# accumulate in f32) agree to a few units of 2**-8 of the largest value
BF16_TOL = 2.0**-5


# ---------------------------------------------------------------------- #
# the array path
# ---------------------------------------------------------------------- #
def array_matmul(n: int) -> None:
    """randn -> standardise -> matmul, bf16, split 0 x split 1."""
    t0 = time.perf_counter()
    ht.random.seed(0)
    a = ht.random.randn(n, n, dtype=ht.bfloat16, split=0)
    b = ht.random.randn(n, n, dtype=ht.bfloat16, split=1)
    z = (a - a.mean(axis=0)) / a.std(axis=0)
    c = ht.matmul(z, b)
    assert c.shape == (n, n) and c.dtype == ht.bfloat16 and c.split == 0, (
        c.shape, c.dtype, c.split)
    _spans_all(c, "matmul result")
    _all_chips_hold_bytes("matmul")
    k = min(n, 256)  # a corner against an f32 product of the same operands
    with jax.default_matmul_precision("highest"):
        ref = z._jarray[:k].astype(jnp.float32) @ b._jarray[:, :k].astype(jnp.float32)
    got = c._jarray[:k, :k]
    err = _rel_err(got, ref)
    assert _finite(got) and err < BF16_TOL, f"matmul corner rel err {err}"
    _done("array.matmul", t0, n=n, dtype="bfloat16", corner_rel_err=f"{err:.2e}")


def array_resplit(n: int) -> None:
    """resplit_ 0 -> 1 -> 0 in place; values exact at each stop."""
    t0 = time.perf_counter()
    ht.random.seed(1)
    x = ht.random.randn(n, n, split=0)
    x0 = ht.copy(x)
    for split in (1, 0):
        x.resplit_(split)
        assert x.split == split
        _spans_all(x, f"resplit_({split})")
        # compared as global arrays by XLA, independently of resplit's tiles
        assert bool(jnp.array_equal(x._jarray, x0._jarray)), f"resplit_({split}) changed values"
    _all_chips_hold_bytes("resplit")
    _done("array.resplit", t0, n=n, dtype="float32", exact=True)


def array_qr(m: int, n: int) -> None:
    """TSQR, mode='r': R'R against A'A."""
    t0 = time.perf_counter()
    ht.random.seed(2)
    a = ht.random.randn(m, n, split=0)
    r = ht.linalg.qr(a, mode="r").R
    assert r.shape == (n, n)
    with jax.default_matmul_precision("highest"):
        ja, jr = a._jarray, r._jarray
        gram = ja.T @ ja
        err = float(jnp.linalg.norm(jr.T @ jr - gram) / jnp.linalg.norm(gram))
    _spans_all(r, "R")
    _all_chips_hold_bytes("qr")
    assert err < 1e-3, f"||R'R - A'A|| / ||A'A|| = {err}"
    _done("array.qr", t0, shape=f"{m}x{n}", dtype="float32", gram_rel_err=f"{err:.2e}")


def array_kmeans(rows: int, d: int, k: int, iters: int = 3) -> None:
    """Lloyd iterations from one seeded start: inertia must not rise."""
    t0 = time.perf_counter()
    ht.random.seed(3)
    x = ht.random.randn(rows, d, split=0)
    inertia = []
    for it in range(1, iters + 1):
        km = ht.cluster.KMeans(n_clusters=k, init="random", max_iter=it, tol=0.0,
                               random_state=0).fit(x)
        assert km.n_iter_ == it, (km.n_iter_, it)
        inertia.append(km.inertia_)
    assert km.cluster_centers_.shape == (k, d) and _finite(km.cluster_centers_._jarray)
    assert km.labels_.shape == (rows,) and km.labels_.split == 0
    _spans_all(km.labels_, "kmeans labels")
    _all_chips_hold_bytes("kmeans")
    assert all(math.isfinite(v) for v in inertia)
    assert all(b <= a * (1 + 1e-6) for a, b in zip(inertia, inertia[1:])), inertia
    _done("array.kmeans", t0, shape=f"{rows}x{d}", k=k,
          inertia="/".join(f"{v:.4e}" for v in inertia))


def array_ragged(rows_per_chip: int = 100) -> None:
    """Rows not a multiple of the device count ride pad-and-mask."""
    t0 = time.perf_counter()
    p = len(jax.devices())
    rows = rows_per_chip * p + 1
    x = ht.arange(rows, dtype=ht.float32, split=0)
    assert x._pad == (-rows) % p and (p == 1 or x._pad > 0), (x._pad, rows, p)
    _spans_all(x, "ragged array")
    got = float((x * 2 + 1).sum())
    assert got == rows**2, got  # the odd numbers below 2·rows; exact in f32 here
    _done("array.ragged", t0, rows=rows, pad=x._pad)


def array_fft(n: int) -> None:
    """complex64 FFT whose result is resident on the device (not the host)."""
    t0 = time.perf_counter()
    ht.random.seed(4)
    x = ht.random.randn(n, split=0).astype(ht.complex64)
    y = ht.fft.fft(x)
    assert y.dtype == ht.complex64 and y.shape == (n,)
    where = {d.platform for d in y._parray.devices()}
    assert where == {jax.devices()[0].platform}, where
    _spans_all(y, "fft result")
    # Parseval, and the inverse transform
    e_x = float(jnp.sum(jnp.abs(x._jarray) ** 2))
    e_y = float(jnp.sum(jnp.abs(y._jarray) ** 2)) / n
    assert abs(e_y - e_x) <= 1e-3 * e_x, (e_x, e_y)
    back = _rel_err(jnp.real(ht.fft.ifft(y)._jarray), jnp.real(x._jarray))
    assert back < 1e-3, back
    _done("array.fft", t0, n=n, dtype="complex64", resident_on="/".join(sorted(where)))


# ---------------------------------------------------------------------- #
# the trainers
# ---------------------------------------------------------------------- #
def _mnist_mlp():
    """BASELINE config[3]'s model: 784-128-64-10 on 28x28 images."""
    return ht.nn.Sequential(
        ht.nn.Flatten(), ht.nn.Linear(784, 128), ht.nn.ReLU(),
        ht.nn.Linear(128, 64), ht.nn.ReLU(), ht.nn.Linear(64, 10),
    )


def train_mlp(batch_per_chip: int, steps: int = 5) -> None:
    """BASELINE config[3]: the MLP under DataParallel + DataParallelOptimizer."""
    t0 = time.perf_counter()
    comm = ht.communication.get_comm()
    opt = ht.optim.DataParallelOptimizer("sgd", lr=0.1)
    dp = ht.nn.DataParallel(_mnist_mlp(), comm=comm, optimizer=opt)
    params = dp.init(jax.random.key(0))
    state = opt.init_state(params)
    step = dp.make_train_step(ht.nn.functional.cross_entropy)
    # seeded MNIST-shaped data: class c lights up image rows 2c..2c+2
    n = batch_per_chip * comm.size
    rng = np.random.default_rng(0)
    y = rng.integers(0, 10, n)
    x = rng.normal(size=(n, 28, 28)).astype(np.float32) * 0.1
    for i, c in enumerate(y):
        x[i, 2 * c : 2 * c + 3] += 1.0
    jx, jy = comm.shard(jnp.asarray(x), 0), comm.shard(jnp.asarray(y), 0)
    losses = []
    for _ in range(steps):
        params, state, loss = step(params, state, jx, jy)
        losses.append(float(loss))
    _spans_all(params, "MLP params")
    assert all(math.isfinite(v) for v in losses) and losses[-1] < losses[0], losses
    _done("train.mlp_dataparallel", t0, global_batch=n,
          loss="/".join(f"{v:.4f}" for v in losses))


def train_daso(model_fn, image, classes: int, batch_per_chip: int, steps: int = 3,
               total_local_comm_size=None, name: str = "train.daso") -> None:
    """BASELINE config[4]: DASO.step on a ('dcn', 'ici') mesh."""
    t0 = time.perf_counter()
    daso = ht.optim.DASO(
        ht.optim.DataParallelOptimizer("sgd", lr=0.01, momentum=0.9),
        total_local_comm_size=total_local_comm_size,
        global_skip=1, stale_steps=1, warmup_steps=1,
    )
    daso.init(model_fn(), key=jax.random.key(0))
    n = batch_per_chip * len(jax.devices())
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(n, *image)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, classes, n))
    losses = [float(daso.step(ht.nn.functional.cross_entropy, x, y))
              for _ in range(steps)]
    _spans_all(daso.parameters, "DASO params")
    _all_chips_hold_bytes("daso")
    assert all(math.isfinite(v) for v in losses), losses
    _done(name, t0, mesh=f"dcn{daso.n_groups}xici{daso.ici_size}",
          per_chip_batch=batch_per_chip, image="x".join(map(str, image)),
          loss="/".join(f"{v:.4f}" for v in losses))


# ---------------------------------------------------------------------- #
# the model layer, kernels engaged
# ---------------------------------------------------------------------- #
def _kernel_counts():
    return path_counts["pallas"], path_counts["dense"]


def _kernels_engaged(before, what: str) -> None:
    pallas, dense = _kernel_counts()
    assert pallas > before[0] and dense == before[1], (
        f"{what}: flash path_counts went {before} -> {(pallas, dense)}; the "
        f"Pallas path must rise and the dense path must not")


def model_lm(cfg: dict, batch: int, seq: int, prompt: int, new: int,
             steps: int = 3) -> None:
    """TransformerLM: jitted value_and_grad steps, then generate."""
    from heat_tpu.nn.models import TransformerLM

    t0 = time.perf_counter()
    before = _kernel_counts()
    comm = ht.communication.get_comm()
    lm = TransformerLM(**cfg)
    params = jax.tree.map(
        lambda a: comm.shard(a.astype(jnp.bfloat16), None), lm.init(jax.random.key(0)))
    tokens = comm.shard(
        jax.random.randint(jax.random.key(1), (batch, seq), 0, cfg["vocab_size"]), 0)

    def loss_fn(p, tok):
        logp = jax.nn.log_softmax(lm.apply(p, tok)[:, :-1].astype(jnp.float32))
        return -jnp.mean(jnp.take_along_axis(logp, tok[:, 1:, None], axis=-1))

    @jax.jit
    def step(p, tok):
        loss, g = jax.value_and_grad(loss_fn)(p, tok)
        return jax.tree.map(lambda w, dw: w - 0.1 * dw.astype(w.dtype), p, g), loss, g

    losses = []
    for _ in range(steps):
        params, loss, grads = step(params, tokens)
        losses.append(float(loss))
    assert all(math.isfinite(v) for v in losses), losses
    assert all(_finite(g) for g in jax.tree.leaves(grads)), "non-finite gradient"
    _spans_all(params, "LM params")
    _kernels_engaged(before, "TransformerLM")
    out = lm.generate(params, tokens[:, :prompt], new)
    assert out.shape == (batch, prompt + new)
    assert bool(jnp.array_equal(out[:, :prompt], tokens[:, :prompt]))
    assert bool(((out >= 0) & (out < cfg["vocab_size"])).all())
    _spans_all(out, "generated tokens")
    _done("model.transformer_lm", t0, batch=batch, seq=seq, depth=cfg["depth"],
          embed=cfg["embed_dim"], vocab=cfg["vocab_size"], dtype="bfloat16",
          loss="/".join(f"{v:.4f}" for v in losses), generated=new)


def _dense_reference(comm, qkv, w, rep: int, window=None):
    """Forward and backward of THE dense softmax path on f32 copies, the
    batch split over the chips and one entry at a time on each, so the
    (S, S) scores of the whole batch never exist at once.  ``rep``: K/V
    heads are repeated to Q's."""
    S, d = qkv[0].shape[-2:]

    def one(args):
        q, k, v, w = (t.astype(jnp.float32) for t in args)

        def f(q, k, v):
            out = _dense_attention(q, jnp.repeat(k, rep, axis=-3),
                                   jnp.repeat(v, rep, axis=-3), True, d**-0.5, S,
                                   window=window)
            return jnp.sum(out * w), out

        (_, out), grads = jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return (out, *grads)

    def per_chip(*ops):
        return tuple(t[:, 0] for t in jax.lax.map(one, tuple(t[:, None] for t in ops)))

    with jax.default_matmul_precision("highest"):
        return jax.jit(comm.shard_map(
            per_chip, in_splits=((4, 0),) * 4, out_splits=((4, 0),) * 4,
        ))(*qkv, w)


def model_flash(shape, kv_heads: int, long_shape) -> None:
    """flash_attention, _gqa (also under a window of a quarter of the
    sequence) and _block, forward and backward, causal bf16, against the dense
    reference on the same device(s); then the long forward."""
    t0 = time.perf_counter()
    comm = ht.communication.get_comm()
    B, H, S, d = shape
    assert B % comm.size == 0, "the batch axis is what the chips share"
    key = jax.random.key(0)

    def rnd(i, heads):
        return comm.shard(
            jax.random.normal(jax.random.fold_in(key, i), (B, heads, S, d), jnp.bfloat16), 0)

    q, k, v, w = rnd(0, H), rnd(1, H), rnd(2, H), rnd(3, H)
    kg, vg = rnd(4, kv_heads), rnd(5, kv_heads)
    pos = jnp.arange(S, dtype=jnp.int32)
    variants = {
        "flash_attention": ((q, k, v), lambda a, b, c: flash_attention(a, b, c, causal=True)),
        "flash_attention_gqa": ((q, kg, vg),
                                lambda a, b, c: flash_attention_gqa(a, b, c, causal=True)),
        "flash_attention_gqa_window": ((q, kg, vg), lambda a, b, c: flash_attention_gqa(
            a, b, c, causal=True, window=S // 4)),
        "flash_attention_block": ((q, k, v), lambda a, b, c: flash_attention_block(
            a, b, c, pos, pos, causal=True, scale=d**-0.5, s_valid=S,
            impl=_block_impl(comm, "flash"))[0]),
    }
    errs = {}
    for name, (ops, fn) in variants.items():
        before = _kernel_counts()

        def loss(a, b, c):
            out = fn(a, b, c)
            return jnp.sum(out.astype(jnp.float32) * w.astype(jnp.float32)), out

        (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(*ops)
        _kernels_engaged(before, name)
        _spans_all((out, grads), name)
        ref = _dense_reference(comm, ops, w, ops[0].shape[1] // ops[1].shape[1],
                               window=S // 4 if name.endswith("_window") else None)
        errs[name] = max(_rel_err(g, r) for g, r in zip((out, *grads), ref))
        assert _finite(out) and errs[name] < BF16_TOL, f"{name} vs dense: {errs[name]}"

    # the long forward: its dense scores alone would not fit the chip
    B2, H2, S2, d2 = long_shape
    axis = 0 if B2 % comm.size == 0 else 1
    ql, kl, vl = (
        comm.shard(jax.random.normal(jax.random.fold_in(key, 9 + i), long_shape,
                                     jnp.bfloat16), axis)
        for i in range(3)
    )
    before = _kernel_counts()
    out = flash_attention(ql, kl, vl, causal=True)
    _kernels_engaged(before, "long forward")
    assert out.shape == long_shape and _finite(out)
    _spans_all(out, "long forward")
    _done("model.flash_attention", t0, shape="x".join(map(str, shape)),
          long="x".join(map(str, long_shape)), dtype="bfloat16",
          **{f"{n}_rel_err": f"{e:.2e}" for n, e in errs.items()})


def model_gated_window(embed: int, heads: int, kv_heads: int, head_dim: int, seq: int, window: int,
                       prefix: int) -> None:
    """One gated windowed attention layer (normalised, rotated heads of their
    own width), forward, bf16, a sequence a chip: the kernels must take it, and
    its first ``prefix`` rows are the dense masked path's rows of that prefix."""
    from heat_tpu.nn.attention import MultiheadAttention

    t0 = time.perf_counter()
    comm = ht.communication.get_comm()
    op = MultiheadAttention(embed, heads, bias=False, rope=True, rope_pairing="half", num_kv_heads=kv_heads,
                            qk_norm=True, head_dim=head_dim, window=window, gate=True)
    params = jax.tree.map(lambda a: comm.shard(a.astype(jnp.bfloat16), None), op.init(jax.random.key(0)))
    x = comm.shard(jax.random.normal(jax.random.key(1), (comm.size, seq, embed), jnp.bfloat16), 0)
    before = _kernel_counts()
    y = jax.jit(lambda p, x: op.apply(p, x, causal=True))(params, x)
    _kernels_engaged(before, "gated windowed attention")
    assert y.shape == x.shape and _finite(y)
    _spans_all(y, "gated windowed attention")
    # causal: a prefix's rows do not depend on what follows; a float mask takes the dense path
    ref = jax.jit(lambda p, x: op.apply(p, x, causal=True, attn_mask=jnp.zeros((prefix, prefix))))(
        params, x[:, :prefix])
    err = _rel_err(y[:, :prefix], ref)
    assert err < BF16_TOL, f"gated windowed attention vs dense: {err}"
    _done("model.gated_window_attention", t0, seq=seq, window=window, heads=f"{heads}/{kv_heads}x{head_dim}",
          embed=embed, dtype="bfloat16", prefix_rel_err=f"{err:.2e}")


def model_kda(shape, chunk: int = 64) -> None:
    """chunk_kda with the Pallas kernels as its chunk-local part, forward and
    backward, bf16 with a float32 decay, against the XLA form of that part on
    the same device(s); the heads are what the chips share."""
    t0 = time.perf_counter()
    comm = ht.communication.get_comm()
    H, S, d = shape
    assert H % comm.size == 0, "the head axis is what the chips share"
    keys = jax.random.split(jax.random.key(11), 6)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(keys[0], shape)) * d**-0.5
    k = unit(jax.random.normal(keys[1], shape))
    v, w = jax.random.normal(keys[2], shape), jax.random.normal(keys[3], shape)
    g = -jax.random.uniform(keys[4], shape, minval=1e-3, maxval=0.5)
    beta = jax.nn.sigmoid(jax.random.normal(keys[5], shape[:2]))
    q, k, v, w = (comm.shard(t.astype(jnp.bfloat16), 0) for t in (q, k, v, w))
    ops = (q, k, v, comm.shard(g, 0), comm.shard(beta, 0))
    before = dict(kda.path_counts)
    out, state = kda.chunk_kda(*ops, chunk=chunk)
    assert kda.path_counts == {**before, "pallas": before["pallas"] + 1}, (
        f"chunk_kda: kda path_counts went {before} -> {kda.path_counts}; the "
        f"Pallas path must rise and the dense path must not")
    tile = kda._pallas_gate(q, v, chunk)

    def both(tile):
        # w an argument: closed over, it would be a constant of the executable
        def loss(w, *a):
            o, final = kda._chunk_kda(*a, chunk, tile)
            return jnp.sum(o.astype(jnp.float32) * w.astype(jnp.float32)) + jnp.sum(final), o

        return jax.jit(jax.value_and_grad(loss, argnums=(1, 2, 3, 4, 5), has_aux=True))(w, *ops)

    ((_, o), grads), ((_, ref), ref_grads) = both(tile), both(0)
    _spans_all((out, state, grads), "chunk_kda")
    err = max(_rel_err(out, ref), _rel_err(o, ref))
    grad_err = max(_rel_err(a, b) for a, b in zip(grads, ref_grads))
    assert _finite(out) and _finite(state), "chunk_kda: not finite"
    assert err < BF16_TOL and grad_err < BF16_TOL, (err, grad_err)
    _done("model.kda", t0, shape="x".join(map(str, shape)), chunk=chunk, tile=tile,
          dtype="bfloat16", kda_rel_err=f"{err:.2e}", kda_grad_rel_err=f"{grad_err:.2e}")


def model_kda_conv(shape, taps: int = 4) -> None:
    """conv_silu_heads (Kimi Delta Attention's convolution, SiLU and L2 norms)
    by its Pallas kernels, forward and backward, bf16, against its dense
    executor on the same device(s); the sequences are what the chips share."""
    t0 = time.perf_counter()
    comm = ht.communication.get_comm()
    per_chip, S, H, d = shape
    keys = jax.random.split(jax.random.key(12), 5)
    qkv = comm.shard(jax.random.normal(keys[0], (per_chip * comm.size, S, 3 * H * d), jnp.bfloat16), 0)
    conv = jax.random.uniform(keys[1], (3 * H * d, taps), minval=-0.5, maxval=0.5)
    ws = [comm.shard(jax.random.normal(k, (per_chip * comm.size, H, S, d), jnp.bfloat16), 0) for k in keys[2:]]
    before = dict(short_conv.path_counts)
    out = short_conv.conv_silu_heads(qkv, conv, H)
    assert short_conv.path_counts == {**before, "pallas": before["pallas"] + 1}, (
        f"conv_silu_heads: path_counts went {before} -> {short_conv.path_counts}; the "
        f"Pallas path must rise and the dense path must not")
    tile = short_conv._pallas_gate(qkv, H, 3)

    def both(tile):
        def loss(qkv, conv, *ws):
            heads = short_conv._conv_silu_heads(qkv, conv, H, (True, True, False), (d**-0.5, 1, 1), 1e-6, tile)
            return sum(jnp.sum(o.astype(jnp.float32) * w.astype(jnp.float32)) for o, w in zip(heads, ws)), heads

        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))(qkv, conv, *ws)

    ((_, heads), grads), ((_, ref), ref_grads) = both(tile), both(None)
    _spans_all((out, grads[0]), "conv_silu_heads")
    err = max(max(_rel_err(a, b), _rel_err(c, b)) for a, b, c in zip(out, ref, heads))
    grad_err = max(_rel_err(a, b) for a, b in zip(grads, ref_grads))
    assert all(map(_finite, out)), "conv_silu_heads: not finite"
    assert err < BF16_TOL and grad_err < BF16_TOL, (err, grad_err)
    _done("model.kda_conv", t0, shape="x".join(map(str, qkv.shape)), heads=H, tile="x".join(map(str, tile)),
          dtype="bfloat16", conv_rel_err=f"{err:.2e}", conv_grad_rel_err=f"{grad_err:.2e}")


# ---------------------------------------------------------------------- #
# more than one chip
# ---------------------------------------------------------------------- #
def multi_dryrun() -> None:
    """Every parallel tier once at toy shapes: the cheap first phase."""
    t0 = time.perf_counter()
    n = len(jax.devices())
    importlib.import_module("__graft_entry__")._dryrun_impl(n, multiproc=False)
    _done("multi.dryrun_tiers", t0, tiers="1-5,7,8")


def multi_ring(shape) -> None:
    """ring_attention(kernel='auto') over the ring against flash on one chip."""
    t0 = time.perf_counter()
    comm = ht.communication.get_comm()
    B, H, s_chip, d = shape
    S = s_chip * comm.size
    key = jax.random.key(7)
    full = [jax.random.normal(jax.random.fold_in(key, i), (B, H, S, d), jnp.bfloat16)
            for i in range(3)]
    before_ring, before = ring_counts["ring"], _kernel_counts()
    out = ring_attention(*(comm.shard(t, 2) for t in full), comm, causal=True)
    assert ring_counts["ring"] == before_ring + 1
    if jax.devices()[0].platform == "tpu":  # 'auto' is the dense block elsewhere
        _kernels_engaged(before, "ring_attention")
    _spans_all(out, "ring output")
    one = jax.devices()[0]
    ref = flash_attention(*(jax.device_put(t, one) for t in full), causal=True)
    err = _rel_err(out, jax.device_put(ref, out.sharding))
    assert _finite(out) and err < BF16_TOL, f"ring vs single-device flash: {err}"
    _done("multi.ring_attention", t0, shape=f"{B}x{H}x{S}x{d}", dtype="bfloat16",
          rel_err=f"{err:.2e}")


def multi_moe(embed: int, hidden: int, experts_per_chip: int, tokens_per_chip: int) -> None:
    """MoE(comm=): experts sharded, tokens through two all_to_alls."""
    t0 = time.perf_counter()
    comm = ht.communication.get_comm()
    E = experts_per_chip * comm.size
    kw = dict(hidden_dim=hidden, top_k=2, capacity_factor=4.0)
    dense, ep = ht.nn.MoE(embed, E, **kw), ht.nn.MoE(embed, E, comm=comm, **kw)
    params = dense.init(jax.random.key(2))
    x = jax.random.normal(jax.random.key(3), (comm.size, tokens_per_chip, embed))
    # 'highest' so that router scores agree to f32 and no token changes expert
    with jax.default_matmul_precision("highest"):
        y = ep.apply(params, x)
        ref = dense.apply(params, x)
    _spans_all(y, "MoE output")
    err = _rel_err(y, ref)
    assert y.shape == x.shape and _finite(y) and err < 1e-3, err
    _done("multi.moe_expert_parallel", t0, experts=E, embed=embed, hidden=hidden,
          tokens=comm.size * tokens_per_chip, rel_err=f"{err:.2e}")


def multi_pipeline(embed: int, heads: int, seq: int, batch_per_chip: int) -> None:
    """Pipelined: one transformer block per chip, GPipe microbatches."""
    from heat_tpu.nn.models import _TransformerBlock

    t0 = time.perf_counter()
    comm = ht.communication.get_comm()
    blk = _TransformerBlock(embed, heads, causal=True)
    pp = ht.nn.Pipelined(blk, comm.size, comm, n_microbatches=comm.size)
    seq_model = ht.nn.Pipelined(blk, comm.size, comm=None)
    params = pp.init(jax.random.key(4))
    x = jax.random.normal(jax.random.key(5), (batch_per_chip * comm.size, seq, embed))
    with jax.default_matmul_precision("highest"):
        y = pp.apply(params, x)
        ref = seq_model.apply(params, x)
    _spans_all(y, "pipeline output")
    err = _rel_err(y, ref)
    assert y.shape == x.shape and _finite(y) and err < 1e-3, err
    _done("multi.pipeline", t0, stages=comm.size, embed=embed, seq=seq,
          batch=x.shape[0], rel_err=f"{err:.2e}")


# ---------------------------------------------------------------------- #
def run(s: dict) -> None:
    """Every phase at the sizes ``s``, in order; the first fault raises."""
    phases = [
        lambda: array_matmul(s["matmul_n"]),
        lambda: array_resplit(s["resplit_n"]),
        lambda: array_qr(*s["qr_shape"]),
        lambda: array_kmeans(*s["kmeans"]),
        array_ragged,
        lambda: array_fft(s["fft_n"]),
        lambda: train_mlp(s["mlp_batch_per_chip"]),
        lambda: train_daso(s["daso_model"], s["daso_image"], s["daso_classes"],
                           s["daso_batch_per_chip"]),
        lambda: model_lm(s["lm"], s["lm_batch"], s["lm_seq"], s["lm_prompt"], s["lm_new"]),
        lambda: model_flash(s["attn"], s["attn_kv_heads"], s["attn_long"]),
        lambda: model_gated_window(**s["gated_window"]),
        lambda: model_kda(s["kda"]),
        lambda: model_kda_conv(s["kda_conv"]),
    ]
    n = len(jax.devices())
    if n > 1:
        phases += [
            multi_dryrun,
            lambda: multi_ring(s["ring"]),
            lambda: multi_moe(**s["moe"]),
            lambda: multi_pipeline(**s["pipe"]),
        ]
    if n > 1 and n % 2 == 0:
        # groups of two: the 'dcn' axis is really crossed (the default makes
        # one group of all chips and never does)
        phases.append(lambda: train_daso(
            _mnist_mlp, (28, 28), 10, s["mlp_batch_per_chip"],
            total_local_comm_size=2, name="multi.daso_two_tier"))
    for phase in phases:
        phase()
        gc.collect()  # the phase's device arrays go before the next one's come


if __name__ == "__main__":
    import os

    from heat_tpu.utils import compile_cache

    cache = compile_cache.configure()
    assert ht.get_device().device_type == "tpu", ht.get_device()
    assert jax.devices()[0].memory_stats() is not None
    dev = _device()

    def entries() -> int:
        return len(os.listdir(cache)) if os.path.isdir(cache) else 0

    at_start = entries()
    print(f"chip_smoke: platform={dev['platform']} device_kind={dev['kind']!r} "
          f"devices={dev['count']} compile_cache={cache} entries={at_start}", flush=True)
    t_all = time.perf_counter()
    run(FULL)
    # a second run over a warm cache adds nothing: every program's key is stable
    print(f"chip_smoke: wall_s_incl_compile={time.perf_counter() - t_all:.1f} "
          f"compile_cache_entries_added={entries() - at_start}", flush=True)
    print("CHIP_SMOKE OK", flush=True)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
