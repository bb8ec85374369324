"""Workload 1 (BASELINE.json configs): ResNet-50 CIFAR-10 dygraph
training, single device (reference: paddle.vision + dygraph loop).

--smoke: tiny subset/model for CI; full mode trains resnet50 properly.
"""
import sys
import os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import argparse
import time

import numpy as np


def main(smoke=True, steps=20, use_jit=None):
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.io import DataLoader
    from paddle_tpu.vision.datasets import Cifar10, FakeData
    from paddle_tpu.vision.models import resnet18, resnet50

    if use_jit is None:
        # full mode on TPU compiles the step (per-op eager dispatch
        # is latency-bound); smoke mode exercises the eager engine
        use_jit = not smoke

    model = resnet18(num_classes=10) if smoke else resnet50(
        num_classes=10)
    opt = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                    parameters=model.parameters(),
                                    weight_decay=5e-4)
    lossf = nn.CrossEntropyLoss()
    try:
        ds = Cifar10(mode="train")
    except FileNotFoundError:
        # zero-egress box without the archive cached: deterministic
        # synthetic CIFAR-shaped data (same item contract)
        ds = FakeData(size=256, image_shape=(3, 32, 32), num_classes=10)
    dl = DataLoader(ds, batch_size=8 if smoke else 256, shuffle=True)

    if smoke:
        # smoke overfits ONE batch (random labels are memorizable) so
        # the loss decrease is a meaningful assertion
        opt.set_lr(0.01)
    model.train()

    def train_step(xb, yb):
        loss = lossf(model(xb), yb)
        opt.clear_grad()
        loss.backward()
        opt.step()
        return loss

    step_fn = paddle.jit.to_static(train_step, objs=[model, opt]) \
        if use_jit else train_step
    losses = []
    t0 = time.time()
    it = iter(dl)
    fixed = next(it) if smoke else None
    for step in range(steps):
        if smoke:
            xb, yb = fixed
        else:
            try:
                xb, yb = next(it)
            except StopIteration:
                it = iter(dl)
                xb, yb = next(it)
        if xb.ndim == 2:                      # flat CIFAR rows
            xb = xb.reshape([xb.shape[0], 3, 32, 32])
        loss = step_fn(xb, yb)
        losses.append(float(loss.numpy()))
    dt = time.time() - t0
    print(f"resnet_cifar10: loss {losses[0]:.3f}->{losses[-1]:.3f} "
          f"({steps / dt:.2f} steps/s)")
    assert losses[-1] < losses[0], "not learning"
    return losses


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    a = ap.parse_args()
    main(a.smoke, a.steps)
