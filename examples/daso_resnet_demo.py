"""DASO hierarchical training demo (BASELINE config[4] shape).

The reference's DASO baseline trains ResNet-50/ImageNet with node-local NCCL
sync every step + async global MPI parameter averaging every k steps
(``heat/optim/dp_optimizer.py::DASO``).  The TPU-native equivalent runs the
same schedule over a ('dcn', 'ici') mesh.  This demo uses a small ResNet on
synthetic image data so it runs anywhere: on the live JAX backend, like the
other examples (``JAX_PLATFORMS=cpu`` gives 8 virtual CPU devices).

Run: python examples/daso_resnet_demo.py
"""

import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import heat_tpu as ht


def main():
    model = ht.nn.models.resnet(stage_sizes=(1, 1), width=16, num_classes=4, in_channels=3)

    opt = ht.optim.DataParallelOptimizer("sgd", lr=0.05, momentum=0.9)
    daso = ht.optim.DASO(opt, global_skip=4, stale_steps=1, warmup_steps=2)
    daso.init(model, key=jax.random.key(0))

    rng = np.random.default_rng(0)
    n, side = 256, 16
    labels = rng.integers(0, 4, n)
    # one bright quadrant per class — linearly separable by a tiny CNN
    x = rng.normal(size=(n, 3, side, side)).astype(np.float32) * 0.1
    h = side // 2
    for i, y in enumerate(labels):
        r, c = divmod(int(y), 2)
        x[i, :, r * h : (r + 1) * h, c * h : (c + 1) * h] += 1.0

    loss_fn = ht.nn.functional.cross_entropy
    for epoch in range(6):
        perm = rng.permutation(n)
        losses = []
        for lo in range(0, n, 64):
            sel = perm[lo : lo + 64]
            losses.append(daso.step(loss_fn, x[sel], labels[sel]))
        print(f"epoch {epoch}: loss {np.mean(losses):.4f}")

    params = daso.consolidated_params()
    # train=True: evaluate with batch statistics (running stats are tracked
    # explicitly via BatchNorm.update_stats in this functional design)
    logits = model.apply(params, x, train=True)
    acc = float(np.mean(np.argmax(np.asarray(logits), axis=1) == labels))
    print(f"train accuracy {acc:.3f}")
    assert acc > 0.8, "DASO demo failed to learn"


if __name__ == "__main__":
    main()
