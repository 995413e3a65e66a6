"""The train driver rehearsed on the CPU at a tiny width with the flash
kernels in interpret mode, and each fault the cell can have planted under the
timed path: a step that returns its state unchanged, half of the batch left
out. The int8 control and each fault must come out as not correct."""
import io
import json

import pytest

from benchmark.lib import harness

import _tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return _tiny.make_root(tmp_path_factory.mktemp("bench") / "root")


@pytest.fixture
def pallas_interpret(monkeypatch, uninstall_mesh):
    from paddle_tpu import kernels

    monkeypatch.setenv("PADDLE_TPU_REMAT_POLICY", "off")
    kernels.set_use_pallas(True)
    yield
    kernels.set_use_pallas(None)


def _run(root, trace=False, **kw):
    out = io.StringIO()
    rc, result = harness.run_cell("mistral7b-train-2k", 2**31 + 78, 2.0, trace,
                                  root=root, require_chip=False, out=out, **kw)
    assert json.loads(out.getvalue().strip().splitlines()[-1])["correct"] \
        == result["correct"]
    return result


def _fails(numbers, limits):
    return [k for k, lim in limits.items() if numbers[k] > lim]


def test_train_run_control_and_half_batch_fault(root, pallas_interpret):
    res = _run(root, control=True)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"train_tokens_per_s_per_chip", "setup_s"}
    assert res["attempted"] >= 1 and res["failed"] == 0
    chk = res["checks"]
    assert chk["compiles_in_window"] == {"value": 0, "limit": 0}
    limits = {k: chk[k]["limit"] for k in
              ("loss_gap_max", "grad_norm_gap_max", "update_norm_gap_max")}
    # the reference put in the program's place, in int8 and on half the rows
    assert _fails(res["control"]["int8"], limits)
    assert "grad_norm_gap_max" in _fails(res["control"]["half_batch"], limits)


def test_traced_run_refuses_device_metrics(root, pallas_interpret):
    res = _run(root, trace=True)
    assert res["correct"] is True and res["metrics"] == {}
    assert res["device"]["busy_s"] == 0.0


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        root, pallas_interpret, monkeypatch):
    from paddle_tpu.optimizer import AdamW

    monkeypatch.setattr(
        AdamW, "apply_gradients",
        lambda self, params, grads, state, lr=None, **kw: (params, state))
    res = _run(root)
    assert res["correct"] is False
    chk = res["checks"]
    # no first moment, no change: both norms read 1 by the measure
    assert chk["grad_norm_gap_max"]["value"] == pytest.approx(1.0)
    assert chk["update_norm_gap_max"]["value"] == pytest.approx(1.0, abs=1e-3)


def test_half_of_the_batch_left_out_is_not_correct(
        root, pallas_interpret, monkeypatch):
    from paddle_tpu.models.llama_pipeline import LlamaPipelineTrainer

    real = LlamaPipelineTrainer.step

    def half(self, x, y):
        return real(self, x[: x.shape[0] // 2], y[: y.shape[0] // 2])

    monkeypatch.setattr(LlamaPipelineTrainer, "step", half)
    res = _run(root)
    assert res["correct"] is False
    chk = res["checks"]["grad_norm_gap_max"]
    assert chk["value"] > chk["limit"]
