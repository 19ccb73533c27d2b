"""``minicpm_sala_9b_train_1x32k_mfu``: per cent of the chip's bf16 peak that
a training step reaches: the model operations of
``jobs/minicpm_sala_train_step.py``'s ``work()`` (6 a parameter a token, the
pairs sparse attention keeps, lightning attention's state; recomputation not
counted) at the peak rate, over the device-busy time of a traced step.  Layer:
trainers."""

from chipbench.harness import roofline


def read(ctx):
    return roofline.job_share(ctx)
