"""Telemetry subsystem tests (DESIGN.md §11).

The load-bearing guarantee: telemetry is *observation only*. Fixed-seed
runs with the metrics bus / run log / trace spans on must be bitwise
identical to runs with them off — sim and LM paths, node-stacked and
sharded drivers (this file runs at 1 device under tier-1 and again at 8
devices in the shard CI job). Plus schema validation for the JSONL run
log and the Chrome trace, the jaxpr audit that the metrics carry adds
no public-stack-shaped intermediate, and the acceptance scenario: one
IDKD run whose run.jsonl alone reconstructs per-node consensus,
thresholds, selected counts, EF residual, and ledger bytes per round.
"""
import json
import logging

import jax
import numpy as np
import pytest

from repro.configs.base import IDKDConfig, TrainConfig
from repro.obs import (EVENT_SCHEMA, RunLog, Telemetry, TraceRecorder, log,
                       read_events, validate_runlog, validate_trace)

from jaxpr_audit import dense_stack_avals

N = 4


# ------------------------------------------------------------ obs.log
def test_log_quiet_under_pytest():
    """Default level resolution sees the pytest env and gates at
    WARNING, so converted print sites stay silent in test runs."""
    assert log._default_level() == logging.WARNING


def test_log_set_level_roundtrip(capsys):
    logger = log.get_logger()
    before = logger.level
    try:
        log.set_level("DEBUG")
        assert logger.isEnabledFor(logging.DEBUG)
        log.set_level(logging.ERROR)
        assert not logger.isEnabledFor(logging.WARNING)
    finally:
        logger.setLevel(before)


# --------------------------------------------------------- obs.runlog
def test_runlog_emit_and_validate(tmp_path):
    path = tmp_path / "run.jsonl"
    rl = RunLog(path)
    rl.emit("run_meta", arch="x")
    rl.emit("metrics", step=10, loss=[1.0] * N, consensus=[0.1] * N)
    rl.emit("run_end", rounds=0)
    rl.close()
    counts = validate_runlog(path)
    assert counts == {"run_meta": 1, "metrics": 1, "run_end": 1}
    evs = read_events(path, "metrics")
    assert evs[0]["step"] == 10 and "t" in evs[0]


def test_runlog_rejects_bad_events(tmp_path):
    rl = RunLog(tmp_path / "run.jsonl")
    with pytest.raises(ValueError, match="unknown"):
        rl.emit("not_a_kind")
    with pytest.raises(ValueError, match="missing required"):
        rl.emit("metrics", step=1)          # no loss/consensus
    rl.close()


def test_validate_runlog_rejects_malformed(tmp_path):
    p = tmp_path / "run.jsonl"
    p.write_text("")
    with pytest.raises(ValueError, match="empty"):
        validate_runlog(p)
    p.write_text("not json\n")
    with pytest.raises(ValueError, match="bad JSON"):
        validate_runlog(p)
    p.write_text(json.dumps({"ev": "mystery", "t": 0.0}) + "\n")
    with pytest.raises(ValueError, match="unknown event"):
        validate_runlog(p)
    p.write_text(json.dumps({"ev": "metrics", "t": 0.0, "step": 1}) + "\n")
    with pytest.raises(ValueError, match="missing required"):
        validate_runlog(p)


# ---------------------------------------------------------- obs.trace
def test_trace_spans_export_and_validate(tmp_path):
    tr = TraceRecorder()
    with tr.span("outer", cat="test", idx=0):
        with tr.span("inner"):
            pass
    out = tmp_path / "trace.json"
    tr.export(out)
    assert validate_trace(out) == 2
    doc = json.loads(out.read_text())
    durs = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
    assert durs["outer"]["dur"] >= durs["inner"]["dur"]
    assert durs["outer"]["args"]["idx"] == 0


def test_span_records_in_current_recorder_with_parent():
    """The span helper records only into the current recorder, with the
    enclosing span as parent; outside ``recording`` (or a tracing
    Telemetry) it records nothing."""
    from repro.obs.trace import current_recorder, recording, span
    rec = TraceRecorder()
    with span("idkd.lost"):
        pass
    with recording(rec):
        assert current_recorder() is rec
        with span("outer", cat="sched", step=1):
            with span("idkd.inner", round=0):
                pass
    assert current_recorder() is None
    ev = {e["name"]: e for e in rec.events}
    assert set(ev) == {"outer", "idkd.inner"}
    assert ev["idkd.inner"]["args"] == {"parent": "outer", "round": 0}
    assert ev["idkd.inner"]["cat"] == "idkd"
    assert ev["outer"]["args"] == {"step": 1}
    # Telemetry(trace=True) holds its recorder current until close()
    tel = Telemetry(None, trace=True)
    with tel.span("segment", cat="train", start=0):
        pass
    assert current_recorder() is tel.tracer
    tel.close()
    assert current_recorder() is None
    assert [e["name"] for e in tel.tracer.events] == ["segment"]


def test_span_shares_the_profiler_clock(tmp_path):
    """A recorded span and its xplane event start within 1 ms: the
    recorder stamps with the wall clock the profiler's host events are
    offsets of (``profile_start_time`` of the Task Environment plane)."""
    from jax.profiler import ProfileData

    from repro.obs.trace import recording, span
    rec = TraceRecorder()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with recording(rec), span("idkd.clock", round=7):
            pass
    finally:
        jax.profiler.stop_trace()
    pd = ProfileData.from_file(str(next(tmp_path.rglob("*.xplane.pb"))))
    start = {p.name: dict(p.stats) for p in pd.planes}[
        "Task Environment"]["profile_start_time"]
    found = [e for p in pd.planes for line in p.lines for e in line.events
             if e.name == "idkd.clock"]
    assert len(found) == 1 and dict(found[0].stats) == {"round": 7}
    recorded_ns = rec.events[0]["ts"] * 1e3
    assert abs(start + found[0].start_ns - recorded_ns) < 1e6


def test_validate_trace_rejects_malformed(tmp_path):
    p = tmp_path / "trace.json"
    p.write_text(json.dumps({"traceEvents": "nope"}))
    with pytest.raises(ValueError):
        validate_trace(p)
    p.write_text(json.dumps(
        {"traceEvents": [{"name": "x", "ph": "X", "ts": 0.0}]}))
    with pytest.raises(ValueError):                 # X without dur/pid
        validate_trace(p)


def test_jax_profile_failure_raises(tmp_path, monkeypatch):
    """A run that asks for a device trace fails when the profiler does
    not start, instead of running on without one."""
    def refuse(log_dir):
        raise RuntimeError("profiler unavailable")

    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    with pytest.raises(RuntimeError, match="profiler unavailable"):
        Telemetry(tmp_path, events=False, jax_profile=True)


# ----------------------------------------------------- obs.check CLI
def test_check_cli(tmp_path):
    from repro.obs.check import main
    assert main([str(tmp_path)]) == 1               # no run.jsonl yet
    rl = RunLog(tmp_path / "run.jsonl")
    rl.emit("run_meta")
    rl.close()
    assert main([str(tmp_path)]) == 0
    assert main([str(tmp_path), "--require-trace"]) == 1
    tr = TraceRecorder()
    with tr.span("s"):
        pass
    tr.export(tmp_path / "trace.json")
    assert main([str(tmp_path), "--require-trace"]) == 0


# ------------------------------------------------- metrics-bus invariant
def test_metrics_update_matches_consensus_distance():
    from repro.core.mixing import consensus_distance
    from repro.obs import metrics as obs_metrics
    rng = np.random.default_rng(0)
    params = {"w": rng.normal(size=(N, 6, 3)).astype(np.float32),
              "b": rng.normal(size=(N, 3)).astype(np.float32)}
    grads = jax.tree.map(np.ones_like, params)
    m = obs_metrics.init_node_metrics(N)
    m = obs_metrics.update(m, np.full((N,), 2.0, np.float32), grads, params)
    s = obs_metrics.summarize(m)
    assert s["accum_steps"] == 1
    np.testing.assert_allclose(
        s["consensus_total"], float(consensus_distance(params)), rtol=1e-5)
    np.testing.assert_allclose(s["loss"], [2.0] * N)
    total = sum(g.reshape(N, -1).sum(1) for g in jax.tree.leaves(grads))
    np.testing.assert_allclose(np.square(s["grad_norm"]) * 1, total,
                               rtol=1e-5)
    m = obs_metrics.reset(m)
    assert int(jax.device_get(m["steps"])) == 0


# ------------------------------------------------------- jaxpr audit
def test_telemetry_step_jaxpr_has_no_public_stack():
    """Extending the PR 5 audit: the metrics update rides the KD step
    without materializing anything shaped like the full public logit
    stack — its intermediates are parameter- and (n,)-shaped only."""
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.core import driver
    from repro.core.algorithms import make_algorithm
    from repro.core.mixing import make_mixer
    from repro.core.topology import Topology
    from repro.launch.steps import stack_params
    from repro.models import build_model
    from repro.obs import metrics as obs_metrics

    n, B, S, P = 2, 2, 8, 16
    cfg = get_config("qwen1.5-0.5b").reduced().replace(
        num_layers=1, d_model=32, num_heads=2, num_kv_heads=2, head_dim=16,
        d_ff=64, vocab_size=64, dtype="float32")
    model = build_model(cfg)
    icfg = IDKDConfig(label_topk=4, kd_weight=0.3)
    step = driver.make_step(model, make_algorithm("qg-dsgdm-n"),
                            make_mixer(Topology.make("ring", n)),
                            driver.lm_sparse_kd_adapter(icfg),
                            telemetry=True)
    assert step.metrics
    params = stack_params(model.init(jax.random.PRNGKey(0)), n)
    opt = step.init_opt(params)
    m0 = obs_metrics.init_node_metrics(n)
    batch = {
        "tokens": jnp.zeros((n, B, S), jnp.int32),
        "labels": jnp.zeros((n, B, S), jnp.int32),
        "pub_tokens": jnp.zeros((n, 2, S), jnp.int32),
        "pub_vals": jnp.zeros((n, 2, S, 4), jnp.float32),
        "pub_idx": jnp.zeros((n, 2, S, 4), jnp.int32),
        "pub_w": jnp.ones((n, 2), jnp.float32),
    }
    jx = jax.make_jaxpr(step)(params, opt, batch,
                              jnp.asarray(0.1, jnp.float32), m0)
    assert not dense_stack_avals(jx.jaxpr, P, cfg.vocab_size)


# ---------------------------------------- on/off trajectory invariance
def _sim_run(driver_mode, telemetry=None, **idkd_kw):
    from repro.configs.resnet20_cifar import SMALL_CONFIG
    from repro.core.simulator import DecentralizedSimulator
    from repro.data.synthetic import (make_classification_data,
                                      make_public_data)
    data = make_classification_data(image_size=8, n_train=256, n_val=64,
                                    n_test=128, noise=0.8, seed=0)
    pub = make_public_data(data, n_public=64, kind="aligned", seed=1)
    tcfg = TrainConfig(algorithm="qg-dsgdm-n", num_nodes=N, alpha=0.05,
                       steps=8, batch_size=8, lr=0.3, seed=4,
                       idkd=IDKDConfig(start_step=4, temperature=10.0,
                                       label_topk=4,
                                       label_backend="sparse", **idkd_kw))
    mcfg = SMALL_CONFIG.replace(image_size=8, conv_backend="im2col")
    sim = DecentralizedSimulator(mcfg, tcfg, data, pub, kd_mode="idkd",
                                 eval_every=4, driver_mode=driver_mode)
    return sim.run(telemetry=telemetry)


@pytest.mark.parametrize("driver_mode", ["scan", "shard"])
def test_sim_trajectory_invariant_under_telemetry(driver_mode, tmp_path):
    """Fixed seeds, telemetry fully on vs fully off: identical
    accuracy / loss / consensus trajectories (scan and shard drivers —
    the shard case re-runs at 8 devices in the CI shard job)."""
    off = _sim_run(driver_mode)
    tel = Telemetry(tmp_path, trace=True, meta={"mode": driver_mode})
    on = _sim_run(driver_mode, telemetry=tel)
    tel.close()
    assert off.acc_history == on.acc_history
    assert off.loss_history == on.loss_history
    assert off.consensus_history == on.consensus_history
    counts = validate_runlog(tmp_path / "run.jsonl")
    assert counts["metrics"] > 0 and counts["accuracy"] > 0
    assert validate_trace(tmp_path / "trace.json") > 0
    # the metrics bus agrees with the host-side eval diagnostics: the
    # flush at each eval boundary reconstructs consensus distance
    flushes = {e["step"]: e for e in read_events(tmp_path / "run.jsonl",
                                                 "metrics")}
    evals = read_events(tmp_path / "run.jsonl", "accuracy")
    for ev, cons in zip(evals, on.consensus_history):
        flush = flushes[ev["step"] + 1]     # eval at stop-1, flush at stop
        np.testing.assert_allclose(flush["consensus_total"], cons,
                                   rtol=1e-4)
        np.testing.assert_allclose(ev["consensus"], cons, rtol=1e-6)


def _lm_run(telemetry=None):
    from repro.configs import get_config
    from repro.launch.train import run_training
    cfg = get_config("qwen1.5-0.5b").reduced().replace(
        num_layers=1, d_model=64, num_heads=2, num_kv_heads=2, head_dim=32,
        d_ff=128, vocab_size=128, dtype="float32")
    tcfg = TrainConfig(num_nodes=2, steps=6, lr=0.1, alpha=0.1,
                       batch_size=4,
                       idkd=IDKDConfig(start_step=3, label_topk=4,
                                       kd_weight=0.3))
    out = run_training(cfg, tcfg, seq_len=16, n_seqs=32, n_public=8,
                       use_idkd=True, log_every=2, verbose=False,
                       telemetry=telemetry)
    return out["loss_history"]


def _tiny_head_reads(*, n, rows_pub, rows_cal, d, vocab, granule=8):
    """Whole-head reads of one streaming round of a tiny f32 LM whose
    public set fits one microbatch: each node's public and calibration
    pass take ``cdiv(rows, tile)`` row tiles, one head read each."""
    from repro.kernels.head_select import BLOCK_C, head_row_tile
    return n * sum(
        -(-rows // head_row_tile(rows, d, min(BLOCK_C, vocab), granule,
                                 np.float32, np.float32))
        for rows in (rows_pub, rows_cal))


def test_lm_trajectory_invariant_under_telemetry(tmp_path):
    off = _lm_run()
    tel = Telemetry(tmp_path, trace=True)
    on = _lm_run(telemetry=tel)
    tel.close()
    assert off == on
    counts = validate_runlog(tmp_path / "run.jsonl")
    assert counts["labels"] == 1 and counts["metrics"] > 0
    lab = read_events(tmp_path / "run.jsonl", "labels")[0]
    assert len(lab["thresholds"]) == 2 and len(lab["selected"]) == 2
    assert 0.0 <= lab["topk_overlap"] <= 1.0
    # the round's compile path rides the labels event, and the program's
    # idkd.* spans nest under the scheduler's label_round span
    from repro.obs.compile_path import KEYS
    assert set(KEYS) <= set(lab) and lab["compiles"] > 0
    assert lab["head_reads"] == _tiny_head_reads(
        n=2, rows_pub=8 * 16, rows_cal=16 * 16, d=64, vocab=128)
    spans = json.loads((tmp_path / "trace.json").read_text())["traceEvents"]
    parents = {e["name"]: e["args"].get("parent") for e in spans
               if e["name"].startswith("idkd.")}
    assert parents["idkd.round"] == "label_round"
    assert parents["idkd.public_pass"] == "idkd.round"


def _tiny_lm_federation():
    """The LM hooks at a tiny size: 2 nodes on a ring, 4 private and 4
    public sequences of 8 tokens, a 1-layer f32 model with d_model 16
    and a 32-token vocabulary. Returns (hooks, params, topology,
    active)."""
    from repro.configs import get_config
    from repro.core.algorithms import make_algorithm
    from repro.core.topology import Topology
    from repro.launch.steps import stack_params
    from repro.launch.train import _LMFederation
    from repro.models import build_model

    n, M, S, V = 2, 4, 8, 32
    cfg = get_config("qwen1.5-0.5b").reduced().replace(
        num_layers=1, d_model=16, num_heads=2, num_kv_heads=2, head_dim=8,
        d_ff=32, vocab_size=V, dtype="float32")
    model = build_model(cfg)
    idkd = IDKDConfig(label_topk=4, label_backend="sparse")
    tcfg = TrainConfig(num_nodes=n, steps=1, batch_size=2, idkd=idkd)
    rng = np.random.default_rng(0)
    fed = _LMFederation(
        model=model, algo=make_algorithm("qg-dsgdm-n"), tcfg=tcfg,
        idkd_cfg=idkd, cfg=cfg,
        tokens=rng.integers(0, V, (n * M, S + 1), dtype=np.int32),
        parts=[np.arange(i * M, (i + 1) * M) for i in range(n)],
        public_tokens=rng.integers(0, V, (4, S), dtype=np.int32),
        seq_len=S, wire_dtype="native", driver_mode="scan", verbose=False)
    params = jax.jit(lambda key: stack_params(model.init(key), n))(
        jax.random.PRNGKey(0))
    return fed, params, Topology.make("ring", n), np.ones(n, bool)


def test_lm_round_spans_and_compile_counters():
    """``on_round`` under a current recorder: the ``idkd.*`` phases nest
    under ``idkd.round``, and ``last_round_stats`` carries the round's
    compile path. A second round at the same shapes compiles only what
    JAX's in-memory caches cannot hold (the streaming round's eager
    scans are lowered anew on every call), with no persistent cache to
    hit."""
    from repro.obs.compile_path import KEYS
    from repro.obs.trace import recording

    fed, params, topo, active = _tiny_lm_federation()
    n = topo.n
    rec = TraceRecorder()
    stats = []
    with recording(rec):
        for r in range(2):
            fed.on_round(params, r, 0, topo, active)
            stats.append(fed.last_round_stats)
    rounds = [e for e in rec.events if e["name"] == "idkd.round"]
    assert [e["args"] for e in rounds] == [
        {"round": r, "nodes": n} for r in range(2)]
    phases = {e["name"]: e["args"]["parent"] for e in rec.events
              if e["name"] != "idkd.round"}
    assert phases == {
        "idkd.inputs": "idkd.round", "idkd.public_pass": "idkd.round",
        "idkd.calibration_pass": "idkd.round",
        "idkd.threshold": "idkd.round", "idkd.exchange": "idkd.round",
        "idkd.readback": "idkd.round", "idkd.topk_overlap": "idkd.round"}
    for st in stats:
        assert set(KEYS) <= set(st)
        assert st["compile_path_s"] == pytest.approx(
            st["trace_s"] + st["lower_s"] + st["backend_compile_s"])
        assert st["cache_hits"] == 0
    first, second = stats
    assert 0 < second["compiles"] < first["compiles"]


def test_lm_round_counts_head_reads_from_shapes(monkeypatch):
    """``last_round_stats["head_reads"]``: whole-head reads of the
    round's ``head_select`` calls, summed over both nodes and both
    passes, from the kernel's own row-tile chooser. Under a scoped-VMEM
    budget of one 8-row granule, each node's 32 public and 32
    calibration rows take 4 row tiles each."""
    from repro.kernels.head_select import kernel
    monkeypatch.setattr(kernel, "VMEM_BUDGET", kernel.head_vmem_bytes(
        8, 16, 32, np.float32, np.float32))
    fed, params, topo, active = _tiny_lm_federation()
    fed.on_round(params, 0, 0, topo, active)
    want = _tiny_head_reads(n=2, rows_pub=4 * 8, rows_cal=4 * 8, d=16,
                            vocab=32)
    assert fed.last_round_stats["head_reads"] == want == 2 * (4 + 4)


# --------------------------------------------- acceptance scenario
def test_acceptance_idkd_run_reconstructs_from_jsonl(tmp_path):
    """ISSUE 8 acceptance: 4 nodes, ring, 2 label rounds, top-k
    compressed gossip, one stale event — the emitted run.jsonl alone
    reconstructs per-node consensus distance, detector thresholds,
    selected counts, EF residual, and ledger bytes per round, and the
    trace JSON is Perfetto-loadable (validates as Chrome trace_event)."""
    from repro import sched
    from repro.configs.resnet20_cifar import SMALL_CONFIG
    from repro.core.simulator import DecentralizedSimulator
    from repro.data.synthetic import (make_classification_data,
                                      make_public_data)
    data = make_classification_data(image_size=8, n_train=256, n_val=64,
                                    n_test=128, noise=1.0, seed=0)
    pub = make_public_data(data, n_public=64, kind="aligned", seed=1)
    mcfg = SMALL_CONFIG.replace(image_size=8, cnn_stages=(1, 1, 1),
                                cnn_width=8, conv_backend="im2col")
    tcfg = TrainConfig(num_nodes=N, steps=12, batch_size=8, seed=4,
                       topology="ring", compression="topk",
                       compression_frac=0.05,
                       idkd=IDKDConfig(start_step=4, every_k_steps=4,
                                       num_rounds=2, label_topk=4,
                                       label_backend="sparse"))
    sim = DecentralizedSimulator(mcfg, tcfg, data, pub, kd_mode="idkd",
                                 eval_every=4)
    schedule = sched.compile_schedule(
        tcfg.steps, 4, round_steps=sim.default_schedule().round_steps,
        events=[sched.ChurnEvent(step=2, down=(3,), mode="stale"),
                sched.ChurnEvent(step=8, up=(3,))], gossip=tcfg.gossip)
    tel = Telemetry(tmp_path, trace=True, meta={"scenario": "acceptance"})
    r = sim.run(schedule=schedule, telemetry=tel)
    tel.close()
    validate_runlog(tmp_path / "run.jsonl")
    assert validate_trace(tmp_path / "trace.json") > 0

    # label rounds: thresholds + per-node selected counts, both rounds
    labels = read_events(tmp_path / "run.jsonl", "labels")
    assert [e["round"] for e in labels] == [0, 1]
    for e in labels:
        assert len(e["thresholds"]) == N and len(e["selected"]) == N
    np.testing.assert_allclose(labels[-1]["thresholds"], r.thresholds,
                               rtol=1e-6)

    # metrics bus: per-node consensus + nonzero EF residual (top-k
    # compression leaves most coordinates in the error-feedback state)
    mets = read_events(tmp_path / "run.jsonl", "metrics")
    assert all(len(e["consensus"]) == N and len(e["ef_residual"]) == N
               for e in mets)
    assert any(max(e["ef_residual"]) > 0 for e in mets)

    # comm events reproduce the ledger's per-round gossip bytes and
    # attribute the stale node (status 1 while step 2..8 was in flight)
    comms = read_events(tmp_path / "run.jsonl", "comm")
    gossip = [e for e in comms if e["kind"] == "gossip"]
    by_round = {}
    for e in gossip:
        by_round[e["round"]] = (by_round.get(e["round"], 0)
                                + sum(e["per_node"]))
    for row in r.ledger["per_round"]:
        if row["gossip_bytes"]:
            np.testing.assert_allclose(by_round[row["round"]],
                                       row["gossip_bytes"])
    assert any(e["status"][3] == 1 for e in gossip)      # stale window
    stale_rows = [row for row in r.ledger["per_round"]
                  if any(row["stale_steps_per_node"])]
    assert stale_rows and stale_rows[0]["stale_steps_per_node"][3] > 0

    # topology events carry the mixing rows under churn
    topo_evs = read_events(tmp_path / "run.jsonl", "topology")
    assert len(topo_evs) == 2
    W = np.asarray(topo_evs[0]["mixing_rows"])
    assert W.shape == (N, N)
    np.testing.assert_allclose(W.sum(1), 1.0, atol=1e-6)


def test_telemetry_off_writes_nothing(tmp_path):
    """A Telemetry with events/metrics disabled is inert — and sim runs
    without the argument never touch the obs layer."""
    tel = Telemetry(None)
    assert tel.runlog is None and tel.tracer is None
    tel.event("run_end")                      # no-op, no crash
    with tel.span("x"):
        pass
    tel.close()
    assert list(tmp_path.iterdir()) == []
