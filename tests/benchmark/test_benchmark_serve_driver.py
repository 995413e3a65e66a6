"""The serve driver rehearsed on the CPU at a tiny width with the kernels in
interpret mode: the harness's look for a chip is skipped and the rest of a
run is driven. Device metrics must be refused there; the int8 control and a
token altered where it is produced must come out as not correct."""
import io
import json
import os
import subprocess
import sys

import pytest

from benchmark.lib import harness

import _tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return _tiny.make_root(tmp_path_factory.mktemp("bench") / "root")


@pytest.fixture
def pallas_interpret(uninstall_mesh):
    from paddle_tpu import kernels

    kernels.set_use_pallas(True)
    yield
    kernels.set_use_pallas(None)


def _run(root, cell, seconds, trace, **kw):
    out = io.StringIO()
    rc, result = harness.run_cell(cell, 2**31 + 77, seconds, trace, root=root,
                                  require_chip=False, out=out, **kw)
    last = out.getvalue().strip().splitlines()[-1]
    assert json.loads(last) == json.loads(json.dumps(result))
    return rc, result


def test_closed_loop_run_control_and_result_line(root, pallas_interpret):
    rc, res = _run(root, "mistral7b-decode-closed", 3.0, False, control=True)
    assert rc == 0 and res["correct"] is True
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"] and list(res)[-1] == "checks"
    # first-token times are per-layer metrics (read in the traced run)
    assert set(res["metrics"]) == {"output_tokens_per_s", "itl_p95_ms",
                                   "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["attempted"] > 4 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"
    chk = res["checks"]
    assert chk["compiles_in_window"] == {"value": 0, "limit": 0}
    assert chk["wrong_length"]["value"] == 0
    # the program (float32 here) is inside the limit, the int8 control is not
    for k in ("logit_gap_max", "logit_gap_mean"):
        assert chk[k]["value"] <= chk[k]["limit"] < res["control"][k + ".int8"]


def test_open_loop_traced_run_refuses_device_metrics(root, pallas_interpret):
    rc, res = _run(root, "mistral7b-chat-open", 3.0, False)
    assert set(res["metrics"]) == {"output_tokens_per_s", "itl_p95_ms",
                                   "setup_s"}
    rc, res = _run(root, "mistral7b-chat-open", 3.0, True)
    assert rc == 0 and res["correct"] is True
    # no TPU plane in the trace: every device metric finds nothing to read
    # only what the client's side counts is left
    assert set(res["metrics"]) == {"loadgen_lag_p95_ms", "ttft_p90_ms.open"}
    assert res["device"]["busy_s"] == 0.0
    assert res["breakdown"] == {"device_ops": [], "idle_gaps": []}


def test_a_token_altered_where_it_is_produced_is_not_correct(
        root, pallas_interpret, monkeypatch):
    from paddle_tpu.serving import engine as engine_mod

    real = engine_mod.sample_logits

    def altered(logits, *a, **kw):
        tok = real(logits, *a, **kw)
        return (tok + 1) % logits.shape[-1]

    monkeypatch.setattr(engine_mod, "sample_logits", altered)
    rc, res = _run(root, "mistral7b-decode-closed", 2.0, False)
    assert res["correct"] is False
    for k in ("logit_gap_max", "logit_gap_mean"):
        assert res["checks"][k]["value"] > res["checks"][k]["limit"]


def test_no_chip_is_an_error_and_prints_no_result(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(_tiny.ROOT, "benchmark", "run.py"),
         "--workload", "mistral7b-decode-closed", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and "not a TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_alone_in_a_directory_is_an_error(root):
    p = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", "mistral7b-decode-closed", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120, cwd=root)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no paddle_tpu package" in p.stderr
