"""End-to-end driver: train a ~100M-param MoE for a few hundred steps with
the full substrate -- synthetic domain-mixture data, UltraEP balancing
every layer/microbatch, async checkpoints, fault-tolerant supervisor.

    PYTHONPATH=src python examples/train_moe_100m.py [--steps 300]
"""

import argparse

from repro.launch.train import train

if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--balancer", default="ultraep")
    args = ap.parse_args()
    # qwen3-235b family reduced to ~100M params: 4 layers, d_model 512,
    # 16 experts -- the structure (GQA + qk_norm + fine-grained MoE top-8)
    # is preserved.
    train("qwen3-235b-a22b", steps=args.steps, batch=8, seq=256,
          reduce=True, d_model=512, num_layers=4, balancer=args.balancer,
          microbatches=2, ckpt_dir="/tmp/repro_100m_ckpt")
