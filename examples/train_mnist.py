"""End-to-end eager + compiled training example (a vision model on a
single chip).  Synthetic data stands in for MNIST when no
local dataset is staged (no network egress).

Run:  python examples/train_mnist.py [--steps 200]
"""
import argparse

import numpy as np

import paddle_tpu as paddle
import paddle_tpu.nn as nn


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=64)
    args = ap.parse_args()

    paddle.seed(0)
    net = nn.Sequential(
        nn.Conv2D(1, 16, 3, padding=1), nn.ReLU(), nn.MaxPool2D(2, 2),
        nn.Conv2D(16, 32, 3, padding=1), nn.ReLU(), nn.MaxPool2D(2, 2),
        nn.Flatten(), nn.Linear(32 * 7 * 7, 10))
    opt = paddle.optimizer.AdamW(1e-3, parameters=net.parameters())

    @paddle.jit.to_static      # whole step -> one XLA program
    def train_step(x, y):
        loss = nn.functional.cross_entropy(net(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    rng = np.random.RandomState(0)
    # synthetic digits: class = quadrant with the bright blob
    for step in range(args.steps):
        y = rng.randint(0, 10, (args.batch,)).astype(np.int64)
        x = rng.rand(args.batch, 1, 28, 28).astype(np.float32) * 0.1
        for i, cls in enumerate(y):
            r, c = divmod(int(cls), 4)
            x[i, 0, 3 + r * 6:9 + r * 6, 3 + c * 6:9 + c * 6] += 1.0
        loss = train_step(paddle.to_tensor(x), paddle.to_tensor(y))
        if step % 20 == 0:
            print(f"step {step}: loss={float(loss.numpy()):.4f}")
    print("final loss:", float(loss.numpy()))
    return float(loss.numpy())


if __name__ == "__main__":
    main()
