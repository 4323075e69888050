"""IDKD on a language model: decentralized next-token training over a
non-IID topic-partitioned corpus with top-k sparse label exchange
(the framework's beyond-paper LLM adaptation, DESIGN.md §3).

    PYTHONPATH=src python examples/llm_idkd_train.py --arch qwen3-1.7b
"""
import argparse

from repro.configs import get_config
from repro.configs.base import IDKDConfig, TrainConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.train import run_training


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b",
                    help="any assigned architecture id")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--nodes", type=int, default=4)
    args = ap.parse_args()
    enable_compile_cache()
    cfg = get_config(args.arch).reduced()
    tcfg = TrainConfig(num_nodes=args.nodes, steps=args.steps, lr=0.1,
                       alpha=0.1, batch_size=8,
                       idkd=IDKDConfig(start_step=args.steps // 2,
                                       label_topk=8, kd_weight=0.3))
    out = run_training(cfg, tcfg, seq_len=48, n_seqs=256, n_public=32,
                       use_idkd=True, log_every=5)
    print(f"loss history: {[round(x, 3) for x in out['loss_history']]}")


if __name__ == "__main__":
    main()
