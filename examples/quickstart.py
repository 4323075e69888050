"""Quickstart: the IDKD framework in ~60 lines.

Builds a 4-node ring, trains the paper's ResNet-EvoNorm on synthetic
non-IID data with QG-DSGDm-N, runs one IDKD homogenization round, and
prints the effect on the class distribution and accuracy.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax.numpy as jnp
import numpy as np

from repro.configs.base import IDKDConfig, TrainConfig
from repro.configs.resnet20_cifar import SMALL_CONFIG
from repro.core.idkd import skew_metric
from repro.core.simulator import DecentralizedSimulator
from repro.data.synthetic import make_classification_data, make_public_data
from repro.launch.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    # 1. synthetic CIFAR-like data + an unlabeled public set
    data = make_classification_data(image_size=8, n_train=1024, n_test=512,
                                    noise=1.6, seed=0)
    public = make_public_data(data, n_public=512, kind="aligned", seed=1)

    # 2. a 4-node ring with highly skewed (Dirichlet α=0.05) private shards
    tcfg = TrainConfig(algorithm="qg-dsgdm-n", topology="ring", num_nodes=4,
                       alpha=0.05, steps=120, batch_size=16, lr=0.5,
                       idkd=IDKDConfig(start_step=80, temperature=10.0))
    mcfg = SMALL_CONFIG.replace(image_size=8)

    # 3. decentralized training with the IDKD homogenization round at step 80
    sim = DecentralizedSimulator(mcfg, tcfg, data, public, kd_mode="idkd",
                                 eval_every=40)
    result = sim.run()

    pre = float(skew_metric(jnp.asarray(result.pre_hist)))
    post = float(skew_metric(jnp.asarray(result.post_hist)))
    print(f"accuracy history : {[round(a, 3) for a in result.acc_history]}")
    print(f"final consensus accuracy: {result.final_acc:.3f}")
    print(f"class-skew (TV from uniform): {pre:.3f} -> {post:.3f}")
    print(f"public samples kept by MSP detector: {result.id_fraction:.2f}")
    print(f"per-node MSP thresholds: {np.round(result.thresholds, 3)}")


if __name__ == "__main__":
    main()
