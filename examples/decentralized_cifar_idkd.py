"""End-to-end driver (deliverable b): trains the paper's model for a few
hundred decentralized steps on a 8-node ring with α=0.05 non-IID data and
compares QG-DSGDm-N, vanilla KD, and QG-IDKD — the paper's Table 2 row at
reduced scale — then saves the consensus checkpoint.

The federation scheduler flags exercise the dynamic settings end to end:
``--rounds K`` re-homogenizes K times (spaced ``every_k_steps`` apart,
fit evenly into the post-start span by default), and ``--churn`` drops
nodes mid-run (``node@down-up`` spec, e.g. ``7@120-200``), with masked
Metropolis gossip holding the survivors doubly stochastic. The per-round
communication ledger is printed for the IDKD run.

    PYTHONPATH=src python examples/decentralized_cifar_idkd.py \
        [--steps 300] [--rounds 3] [--churn 7@120-200]
"""
import argparse

import jax.numpy as jnp

from repro import sched
from repro.checkpoint import save_checkpoint
from repro.configs.base import IDKDConfig, TrainConfig
from repro.configs.resnet20_cifar import SMALL_CONFIG
from repro.core.idkd import skew_metric
from repro.core.simulator import DecentralizedSimulator
from repro.data.synthetic import make_classification_data, make_public_data
from repro.launch.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--nodes", type=int, default=8)
    ap.add_argument("--alpha", type=float, default=0.05)
    ap.add_argument("--seed", type=int, default=4)   # paper seeds: 4, 34, 5
    ap.add_argument("--rounds", type=int, default=1,
                    help="IDKD homogenization rounds (re-labeled each time)")
    ap.add_argument("--every-k", type=int, default=0,
                    help="steps between rounds (default: fit evenly)")
    ap.add_argument("--churn", default="",
                    help="churn spec node@down-up[,...], e.g. 7@120-200")
    ap.add_argument("--telemetry", default="", metavar="DIR",
                    help="write the QG-IDKD run's telemetry (run.jsonl + "
                         "trace.json, DESIGN.md §11) under DIR")
    args = ap.parse_args()
    enable_compile_cache()

    data = make_classification_data(image_size=8, n_train=1024, n_val=256,
                                    n_test=512, noise=2.2, seed=0)
    public = make_public_data(data, n_public=768, kind="aligned", seed=1)
    mcfg = SMALL_CONFIG.replace(image_size=8)
    start = int(args.steps * 0.6)
    every_k = args.every_k or sched.fit_every_k(args.steps, start,
                                                args.rounds)
    churn = (sched.parse_churn(args.churn, args.nodes, args.steps)
             if args.churn else ())

    results = {}
    for name, (algo, kd) in {
        "QG-DSGDm-N": ("qg-dsgdm-n", None),
        "QG-DSGDm-N + KD": ("qg-dsgdm-n", "vanilla"),
        "QG-IDKD (ours)": ("qg-dsgdm-n", "idkd"),
    }.items():
        tcfg = TrainConfig(algorithm=algo, num_nodes=args.nodes,
                           alpha=args.alpha, steps=args.steps, batch_size=16,
                           lr=0.5, seed=args.seed,
                           idkd=IDKDConfig(start_step=start,
                                           temperature=10.0,
                                           every_k_steps=every_k,
                                           num_rounds=args.rounds))
        sim = DecentralizedSimulator(mcfg, tcfg, data, public, kd_mode=kd,
                                     eval_every=max(args.steps // 6, 1))
        schedule = sched.compile_schedule(
            tcfg.steps, sim.eval_every,
            round_steps=sim.default_schedule().round_steps, events=churn)
        telemetry = None
        if args.telemetry and kd == "idkd":
            from repro.obs import Telemetry
            telemetry = Telemetry(args.telemetry, trace=True,
                                  meta={"method": name, "steps": args.steps,
                                        "nodes": args.nodes,
                                        "alpha": args.alpha})
        try:
            r = sim.run(schedule=schedule, telemetry=telemetry)
        finally:
            if telemetry is not None:
                telemetry.close()
        results[name] = r
        extra = ""
        if r.post_hist is not None:
            extra = (f"  skew {float(skew_metric(jnp.asarray(r.pre_hist))):.3f}"
                     f"->{float(skew_metric(jnp.asarray(r.post_hist))):.3f}"
                     f"  id_frac {r.id_fraction:.2f}"
                     f"  rounds {len(r.rounds)}")
        print(f"{name:18s} acc={r.final_acc*100:6.2f}%  "
              f"curve={[round(a, 2) for a in r.acc_history]}{extra}",
              flush=True)

    idkd_run = results["QG-IDKD (ours)"]
    print("\nper-round communication ledger (QG-IDKD):")
    for row in idkd_run.ledger["per_round"]:
        print(f"  round {row['round']}: {row['gossip_bytes']/1e6:8.2f} MB "
              f"gossip over {row['steps']} steps, "
              f"{row['labels_bytes']/1e3:8.2f} kB labels")

    best = max(results.items(), key=lambda kv: kv[1].final_acc)
    print(f"\nbest method: {best[0]} ({best[1].final_acc*100:.2f}%)")
    save_checkpoint("experiments/e2e_consensus", best[1].__dict__.get(
        "params", {"acc": jnp.asarray(best[1].final_acc)}), step=args.steps)
    print("checkpoint written to experiments/e2e_consensus.npz")


if __name__ == "__main__":
    main()
