"""Hybrid-parallel Llama pretraining example.

Single chip:       python examples/pretrain_llama.py
8 virtual devices: JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/pretrain_llama.py --dp 2 --mp 2 --sharding 2
"""
import argparse

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.core.compile_cache import enable_compile_cache
from paddle_tpu.distributed import fleet
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--mp", type=int, default=1)
    ap.add_argument("--sharding", type=int, default=1)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    args = ap.parse_args()
    enable_compile_cache()      # before the first jit

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": args.dp, "mp_degree": args.mp,
                               "sharding_degree": args.sharding}
    if args.sharding > 1:
        strategy.sharding_configs = {"stage": 3}
    fleet.init(is_collective=True, strategy=strategy)

    paddle.seed(0)
    cfg = LlamaConfig.tiny(max_position_embeddings=args.seq)
    model = fleet.distributed_model(LlamaForCausalLM(cfg))
    opt = fleet.distributed_optimizer(
        paddle.optimizer.AdamW(3e-4, parameters=model.parameters()))

    @paddle.jit.to_static
    def train_step(tokens):
        loss, _ = model(tokens, labels=tokens)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    rng = np.random.RandomState(0)
    for step in range(args.steps):
        tokens = paddle.to_tensor(rng.randint(
            0, cfg.vocab_size, (args.batch, args.seq)).astype(np.int32))
        loss = train_step(tokens)
        print(f"step {step}: loss={float(loss.numpy()):.4f}")
    return float(loss.numpy())


if __name__ == "__main__":
    main()
