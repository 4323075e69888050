"""The label-round cell's check, at a size the CPU holds: a sound run is
correct, the control (the reference at fp8 in the program's place) is
not, and each planted fault makes ``correct`` come out false."""
import jax.numpy as jnp

from _tiny import limits, run_tiny

W = "qwen3-1.7b.label_rounds"


def failed(numbers: dict) -> bool:
    lim = limits(W)
    return any(numbers[k] > lim[k] for k in lim)


def test_sound_run_is_correct_and_the_control_is_not():
    result, cell = run_tiny(W)
    assert result["correct"], result["checks"]
    assert list(result)[-1] == "checks"
    nums = cell.numbers(with_control=True)
    assert not failed(nums["program"])
    assert failed(nums["control"]), nums["control"]


def test_altered_answer(monkeypatch):
    from repro.core import labeling
    oracle = labeling._stream_oracle

    def altered(*a, **k):
        conf, vals, idx = oracle(*a, **k)
        V = a[1].shape[-1]
        return conf, vals, idx.at[:, 0].set((idx[:, 0] + 1) % V)

    monkeypatch.setattr(labeling, "_stream_oracle", altered)
    assert not run_tiny(W)[0]["correct"]


def test_exchange_left_out(monkeypatch):
    from repro.core import distill, labeling
    exchange = labeling.exchange_sparse

    def own_only(topology, id_mask, sparse):
        merged, weights = exchange(topology, id_mask, sparse)
        k = sparse.values.shape[-1]
        vals = merged.values.at[..., k:].set(0.0)
        return distill.SparseLabels(vals, merged.indices), weights

    monkeypatch.setattr(labeling, "exchange_sparse", own_only)
    assert not run_tiny(W)[0]["correct"]


def test_half_the_public_batch_left_out(monkeypatch):
    from repro.core import labeling
    chunk = labeling._chunk_public

    def half(public_x, microbatch):
        chunks, P, mb = chunk(public_x, microbatch)
        h = chunks.shape[1] // 2
        return jnp.concatenate([chunks[:, :h], chunks[:, :h]], 1), P, mb

    monkeypatch.setattr(labeling, "_chunk_public", half)
    assert not run_tiny(W)[0]["correct"]


def test_half_the_calibration_set_left_out(monkeypatch):
    """Each node's threshold swept from half its calibration sequences.
    The threshold is the only output of the calibration pass, so the
    fault shows where the half moves it off every Youden optimum of the
    whole set; on traffic whose calibration and public sequences come
    from one distribution that holds on some draws only, this one among
    them (``selection_gap`` 0.057 here)."""
    from repro.core import labeling
    val_conf = labeling._stream_val_conf

    def half(model, params, val_x, cfg, **kw):
        return val_conf(model, params, val_x[:, :val_x.shape[1] // 2],
                        cfg, **kw)

    monkeypatch.setattr(labeling, "_stream_val_conf", half)
    result = run_tiny(W, seed=24)[0]
    assert not result["correct"]
    assert result["checks"]["selection_gap"]["value"] > \
        limits(W)["selection_gap"]
