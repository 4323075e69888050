"""Smoke run of the IDKD trainer on a TPU, through the entry points a
user calls, with random weights made from a seed.

One chip (no arguments):

* Phase A — the LM trainer, ``repro.launch.train.run_training``:
  qwen3-1.7b at its published widths (d_model 2048, 16/8 heads,
  head_dim 128, d_ff 6144, vocab 151,936, bf16), cut in depth only; two
  nodes on a ring, per-node batch 8, nine scan-driver steps with one
  IDKD homogenization round before step 5.
* Phase B — the paper's simulator, ``DecentralizedSimulator``:
  resnet20-evonorm at its paper size on 32×32×3 inputs, 16 nodes on a
  ring, nine steps with one IDKD round, ``driver_mode="auto"``.

Four chips (``--four-chips``, a 2x2 v5e host) run only the paths that
exist across chips:

* the LM trainer with ``driver_mode="shard"``, one node per chip;
* the LM trainer on the ``(node, model)`` mesh (``model_parallel=2``)
  against the same seed on the scan driver on one chip;
* the simulator with the shard driver on a 4-device node mesh against
  the same seed on the scan driver on one chip (four stacked qwen3
  replicas do not fit one chip, so the 4-node comparison uses the
  paper's model).

Each phase prints what was cut, compile seconds, steady seconds per
step (information only), losses before and after the round, the
in-distribution fraction, and evidence that the label round ran the
compiled Pallas kernel. A failed check exits non-zero. The last line of
standard output is ``{"ok": true, "device": {...}}``.

    python chip_smoke.py [--four-chips]
"""
from __future__ import annotations

import argparse
import json
import math
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT_DIR = ROOT / "chiprun_out" / "chip_smoke"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# Phase A: depth is the only cut of the model; sequence length and batch
# are chosen to fit two node replicas (params, momentum, grads, logits)
# in one v5e chip's 16 GB — the compile rehearsal's memory_analysis puts
# the KD step at 12.7 GiB for 3 layers at S=128 (14.2 GiB at 4 layers)
LM_ARCH = "qwen3-1.7b"
LM_LAYERS = 3
LM_SEQ_LEN = 128
LM_BATCH = 8
LM_NODES = 2
# evals after steps 0, 2, 4, 6, 8 and the round before step 5 cut the run
# into segments [0,1) [1,3) [3,5) | round | [5,7) [7,9): [3,5) and [7,9)
# reuse the runners [1,3) and [5,7) compiled, so they time steady steps
STEPS = 9
LOG_EVERY = 2
ROUND_STEP = 5
# agreement across drivers/meshes: bf16 training, nine steps
LOSS_RTOL = 2e-2


class Check(Exception):
    """A smoke check failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise Check(what)


def say(tag: str, msg: str) -> None:
    print(f"[{tag}] {msg}", flush=True)


class CompileClock:
    """Sums XLA backend compile time reported by ``jax.monitoring``."""

    def __init__(self):
        self.seconds = 0.0
        self.count = 0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.seconds += duration
            self.count += 1

    def since(self, mark):
        return self.seconds - mark[0], self.count - mark[1]

    def mark(self):
        return self.seconds, self.count


def steady_seconds_per_step(run_log: Path):
    """Host seconds per step of the last evaluated segment before and
    after the round: from the segment's dispatch to the eval that syncs
    on its last loss (see ``STEPS``)."""
    from repro.obs import read_events
    evals = {e["step"]: e["t"] for e in read_events(run_log, "eval")}
    out = {}
    for seg in read_events(run_log, "segment"):
        phase = "plain" if seg["stop"] <= ROUND_STEP else "kd"
        end = evals.get(seg["stop"] - 1)
        if end is not None:
            out[phase] = (end - seg["t"]) / seg["steps"]
    return out


def label_fraction(run_log: Path) -> float:
    from repro.obs import read_events
    labels = read_events(run_log, "labels")
    check(len(labels) == 1, f"expected one label round, got {len(labels)}")
    return float(labels[0]["id_fraction"])


def check_losses(tag: str, losses, run_log: Path) -> None:
    from repro.obs import read_events
    eval_steps = [e["step"] for e in read_events(run_log, "eval")]
    check(len(eval_steps) == len(losses), "one loss per eval")
    before = [x for s, x in zip(eval_steps, losses) if s < ROUND_STEP]
    after = [x for s, x in zip(eval_steps, losses) if s >= ROUND_STEP]
    say(tag, f"losses before the round {before}; after {after}")
    check(bool(before) and bool(after), "losses on both sides of the round")
    check(all(math.isfinite(x) for x in losses), f"finite losses: {losses}")


def check_fraction(tag: str, frac: float) -> None:
    say(tag, f"id_fraction {frac}")
    check(0.0 < frac < 1.0, f"0 < id_fraction < 1, got {frac}")


def fresh_dir(tag: str) -> Path:
    """The phase's telemetry directory, emptied (run logs append)."""
    out_dir = OUT_DIR / tag
    shutil.rmtree(out_dir, ignore_errors=True)
    return out_dir


def kernel_calls(lowered_text: str) -> int:
    return lowered_text.count("tpu_custom_call")


# ------------------------------------------------------------ LM trainer
def lm_config(layers: int):
    from repro.configs import get_config
    published = get_config(LM_ARCH)
    return published, published.replace(num_layers=layers)


def lm_run(tag: str, clock: CompileClock, *, layers: int, seq_len: int,
           batch: int, nodes: int, driver_mode: str,
           model_parallel: int = 1):
    """One ``run_training`` call at published widths, checked; returns
    its output and the ``TrainConfig``."""
    from repro.configs.base import IDKDConfig, TrainConfig
    from repro.launch.train import run_training
    from repro.obs import Telemetry

    published, cfg = lm_config(layers)
    changed = {f: (getattr(published, f), getattr(cfg, f))
               for f in published.__dataclass_fields__
               if getattr(published, f) != getattr(cfg, f)}
    check(set(changed) == {"num_layers"}, f"only depth is cut: {changed}")
    say(tag, f"{LM_ARCH} reductions: num_layers {published.num_layers} -> "
             f"{cfg.num_layers} (depth), seq_len {seq_len} (sequence), "
             f"per-node batch {batch} (batch); published widths kept: "
             f"d_model {cfg.d_model}, heads {cfg.num_heads}/"
             f"{cfg.num_kv_heads}, head_dim {cfg.head_dim}, d_ff "
             f"{cfg.d_ff}, vocab {cfg.vocab_size}, {cfg.dtype}")
    say(tag, f"{nodes} nodes on a ring, {STEPS} steps, driver "
             f"{driver_mode}, model_parallel {model_parallel}")
    tcfg = TrainConfig(num_nodes=nodes, steps=STEPS, lr=0.1, alpha=0.1,
                       batch_size=batch, topology="ring",
                       idkd=IDKDConfig(start_step=ROUND_STEP, label_topk=8,
                                       label_backend="sparse",
                                       every_k_steps=STEPS, num_rounds=1))
    out_dir = fresh_dir(tag)
    tel = Telemetry(out_dir, metrics=False, meta={"phase": tag})
    mark = clock.mark()
    t0 = time.perf_counter()
    try:
        out = run_training(cfg, tcfg, seq_len=seq_len, log_every=LOG_EVERY,
                           use_idkd=True, verbose=False,
                           driver_mode=driver_mode,
                           model_parallel=model_parallel, telemetry=tel)
    finally:
        tel.close()
    wall = time.perf_counter() - t0
    compile_s, compiles = clock.since(mark)
    run_log = out_dir / "run.jsonl"
    say(tag, f"wall {wall:.3f} s, XLA compile {compile_s:.3f} s over "
             f"{compiles} programs")
    say(tag, "steady s/step, host clock from segment dispatch to the "
             "eval that syncs after it (information only): "
             f"{steady_seconds_per_step(run_log)}")
    check_losses(tag, out["loss_history"], run_log)
    check_fraction(tag, label_fraction(run_log))
    return out, tcfg


def lm_kernel_evidence(tag: str, out, tcfg, seq_len: int) -> None:
    """Lower the streaming label round the trainer ran, at its shapes, on
    this backend and count the Mosaic kernel calls in it."""
    import jax
    import jax.numpy as jnp

    from repro.core import labeling

    params = jax.tree.map(lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype),
                          out["node_params"])
    n = tcfg.num_nodes
    # run_training's public set (n_public=64) and the up-to-16 private
    # sequences per node its label round calibrates on
    pub = jax.ShapeDtypeStruct((64, seq_len), jnp.int32)
    priv = jax.ShapeDtypeStruct((n, 16, seq_len), jnp.int32)
    text = jax.jit(lambda p, x, v: labeling.streaming_label_round(
        out["model"], p, x, v, out["topology"], tcfg.idkd)).lower(
            params, pub, priv).as_text()
    calls = kernel_calls(text)
    say(tag, f"label round lowers to {calls} tpu_custom_call(s) "
             "(compiled head_select, not interpret mode or the jnp oracle)")
    check(calls > 0, "head_select compiled into the LM label round")


def phase_lm(clock: CompileClock) -> None:
    out, tcfg = lm_run("A lm", clock, layers=LM_LAYERS, seq_len=LM_SEQ_LEN,
                       batch=LM_BATCH, nodes=LM_NODES, driver_mode="scan")
    lm_kernel_evidence("A lm", out, tcfg, LM_SEQ_LEN)


# ------------------------------------------------------------- simulator
SIM_NODES = 16
SIM_BATCH = 32
# the simulator's samplers close over the training set, so every compiled
# runner embeds it as a constant: 50,000 32x32x3 images made each runner
# a 1 GB executable and ~60 s of compile on the chip; 8,192 keep the
# smoke inside a few minutes
SIM_TRAIN = 8192


def sim_run(tag: str, clock: CompileClock, *, nodes: int,
            driver_mode: str = "auto", model_parallel: int = 1,
            image_size: int = 32, n_train: int = SIM_TRAIN):
    """One ``DecentralizedSimulator.run`` of resnet20-evonorm."""
    from repro.configs.base import IDKDConfig, TrainConfig
    from repro.configs.resnet20_cifar import CONFIG
    from repro.core.simulator import DecentralizedSimulator
    from repro.data.synthetic import (make_classification_data,
                                      make_public_data)
    from repro.obs import Telemetry

    mcfg = CONFIG.replace(image_size=image_size)
    say(tag, f"{mcfg.name} at paper size: stages {mcfg.cnn_stages}, width "
             f"{mcfg.cnn_width}, {mcfg.image_size}x{mcfg.image_size}x"
             f"{mcfg.image_channels}, {mcfg.num_classes} classes; "
             f"reductions: {STEPS} steps (length of the run), "
             f"{n_train} synthetic CIFAR-shaped train images (of 50,000)")
    data = make_classification_data(image_size=mcfg.image_size,
                                    n_train=n_train, n_val=512,
                                    n_test=1024, noise=1.6, seed=0)
    public = make_public_data(data, n_public=1024, kind="aligned", seed=1)
    tcfg = TrainConfig(algorithm="qg-dsgdm-n", topology="ring",
                       num_nodes=nodes, alpha=0.1, steps=STEPS,
                       batch_size=SIM_BATCH, lr=0.1, seed=4,
                       idkd=IDKDConfig(start_step=ROUND_STEP,
                                       temperature=10.0,
                                       label_backend="sparse",
                                       every_k_steps=STEPS, num_rounds=1))
    sim = DecentralizedSimulator(mcfg, tcfg, data, public, kd_mode="idkd",
                                 eval_every=LOG_EVERY,
                                 driver_mode=driver_mode,
                                 model_parallel=model_parallel)
    say(tag, f"{nodes} nodes on a ring, batch {SIM_BATCH}, driver "
             f"{driver_mode} -> {sim.driver_mode}, model_parallel "
             f"{model_parallel}")
    out_dir = fresh_dir(tag)
    tel = Telemetry(out_dir, metrics=False, meta={"phase": tag})
    mark = clock.mark()
    t0 = time.perf_counter()
    try:
        res = sim.run(telemetry=tel)
    finally:
        tel.close()
    wall = time.perf_counter() - t0
    compile_s, compiles = clock.since(mark)
    run_log = out_dir / "run.jsonl"
    say(tag, f"wall {wall:.3f} s, XLA compile {compile_s:.3f} s over "
             f"{compiles} programs")
    say(tag, "steady s/step, host clock from segment dispatch to the "
             "eval that syncs after it (information only): "
             f"{steady_seconds_per_step(run_log)}")
    check_losses(tag, res.loss_history, run_log)
    say(tag, f"test accuracy {res.acc_history}")
    check_fraction(tag, label_fraction(run_log))
    return sim, res


def sim_kernel_evidence(tag: str, sim) -> None:
    import jax

    from repro.core import labeling

    params = jax.eval_shape(sim._stacked_init)
    val = sim._per_node_val_inputs()
    text = jax.jit(lambda p, x, v: labeling.streaming_label_round(
        sim.model, p, x, v, sim.topology, sim.tcfg.idkd)).lower(
            params, sim.public_x, val).as_text()
    calls = kernel_calls(text)
    say(tag, f"label round lowers to {calls} tpu_custom_call(s) "
             "(compiled head_select at the ResNet head)")
    check(calls > 0, "head_select compiled into the simulator label round")


def phase_sim(clock: CompileClock) -> None:
    sim, _ = sim_run("B sim", clock, nodes=SIM_NODES)
    check(sim.driver_mode == "scan", "auto resolves to scan on the chip")
    sim_kernel_evidence("B sim", sim)


# ------------------------------------------------------------ four chips
def agree(tag: str, ref, got) -> None:
    diff = max(abs(a - b) / max(abs(a), 1e-6) for a, b in zip(ref, got))
    say(tag, f"max relative loss difference {diff:.3e} "
             f"(tolerance {LOSS_RTOL})")
    check(len(ref) == len(got) and diff <= LOSS_RTOL,
          f"losses agree within {LOSS_RTOL}: {ref} vs {got}")


def node_devices(tag: str, node_params, nodes: int) -> None:
    """Every node-stacked leaf holds one node per device, on ``nodes``
    distinct devices."""
    import jax
    seen = set()
    for leaf in jax.tree.leaves(node_params):
        if leaf.ndim == 0 or leaf.shape[0] != nodes:
            continue
        devs = {s.device for s in leaf.addressable_shards}
        check(len(devs) == nodes,
              f"leaf {leaf.shape} on {len(devs)} devices, want {nodes}")
        check(leaf.sharding.shard_shape(leaf.shape)[0] == 1,
              f"leaf {leaf.shape} holds one node per device")
        seen |= devs
    say(tag, f"node-stacked params sit on devices "
             f"{sorted(d.id for d in seen)}")
    check(len(seen) == nodes, "nodes spread over distinct devices")


def phase_four_chips(clock: CompileClock) -> None:
    out, _ = lm_run("C lm shard", clock, layers=LM_LAYERS,
                    seq_len=LM_SEQ_LEN, batch=LM_BATCH, nodes=4,
                    driver_mode="shard")
    node_devices("C lm shard", out["node_params"], 4)
    del out
    mesh, _ = lm_run("C lm mesh2x2", clock, layers=LM_LAYERS,
                     seq_len=LM_SEQ_LEN, batch=LM_BATCH, nodes=LM_NODES,
                     driver_mode="shard", model_parallel=2)
    mesh_losses = mesh["loss_history"]
    del mesh
    ref, _ = lm_run("C lm scan1", clock, layers=LM_LAYERS,
                    seq_len=LM_SEQ_LEN, batch=LM_BATCH, nodes=LM_NODES,
                    driver_mode="scan")
    agree("C lm mesh2x2 vs scan1", ref["loss_history"], mesh_losses)
    del ref
    _, shard = sim_run("C sim shard", clock, nodes=4, driver_mode="shard")
    _, ref = sim_run("C sim scan1", clock, nodes=4, driver_mode="scan")
    agree("C sim shard vs scan1", ref.loss_history, shard.loss_history)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the multi-chip paths (needs 4 chips)")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU chip found (jax.devices()[0].platform "
              f"is {dev.platform!r})", file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} TPU chips, found {len(devices)}",
              file=sys.stderr)
        return 1
    say("device", f"{dev.platform} {dev.device_kind} x{len(devices)}; "
                  f"jax {jax.__version__}; compile cache {cache}")
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    phases = ([phase_four_chips] if args.four_chips
              else [phase_lm, phase_sim])
    try:
        for phase in phases:
            phase(clock)
    except Check as e:
        print(f"chip_smoke: check failed: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
