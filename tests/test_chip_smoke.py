"""CPU rehearsal of ``chip_smoke.py``: the same legs at a tiny width with the
Pallas kernels forced on in interpret mode — flash forward/backward inside the
trainer's step, paged attention inside the engine's decode. It is the run a
builder makes before spending chip time, and the one end-to-end case per
kernel that makes the program tested the program shipped. The script's own
behaviour without a chip (no CPU mode, no result line) is checked too.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

from paddle_tpu import kernels

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402

WIDTHS = dict(vocab_size=512, hidden_size=128, intermediate_size=256,
              num_attention_heads=4, num_key_value_heads=4,
              max_position_embeddings=256)
TRAIN = dict(widths=WIDTHS, layers=2, batch=4, seq=128)
SERVE = dict(widths=WIDTHS, layers=2, block_size=8, max_slots=4,
             max_model_len=256, prompt_lens=(10, 40, 150), shared_prefix=96,
             tail_len=20, new_tokens=4, paged_slots=8)


@pytest.fixture
def pallas_interpret(monkeypatch, uninstall_mesh):
    # the trainer reads the policy when it builds its step; the leg sets it
    monkeypatch.setenv("PADDLE_TPU_REMAT_POLICY", "off")
    kernels.set_use_pallas(True)
    yield
    kernels.set_use_pallas(None)


def test_train_leg_one_and_four_devices(pallas_interpret):
    one = chip_smoke.train_leg(TRAIN, {"dp": 1}, steps=3)
    assert len(one["losses"]) == 3 and one["params"] > 0
    # the same seed, batches and global batch on dp2 x mp2 of the virtual CPU
    # devices: sharding is checked, and the losses must match
    four = chip_smoke.train_leg(TRAIN, {"dp": 2, "mp": 2}, steps=3,
                                ref_losses=one["losses"])
    assert four["sharded_arrays"] > 0 and len(four["bytes_in_use"]) == 4


def test_serve_leg(pallas_interpret):
    out = chip_smoke.serve_leg(SERVE)
    assert out["requests"] == 6 and out["tokens"] == 6 * SERVE["new_tokens"]
    assert out["prefix_hits"] >= 1


def test_replaced_kernel_is_caught(tmp_path):
    """The IR check is what tells a kernel from its composition."""
    (tmp_path / "jax_ir0_jit_decode_compile.mlir").write_text(
        'stablehlo.dot_general loc("jit(decode)/dot_general")\n')
    with pytest.raises(chip_smoke.SmokeFailure, match="paged_attention"):
        chip_smoke.kernels_in_step(str(tmp_path), "decode",
                                   ("paged_attention",), mosaic=False)
    (tmp_path / "jax_ir1_jit_decode_compile.mlir").write_text(
        'loc("jit(decode)/paged_attention/pallas_call")\n')
    chip_smoke.kernels_in_step(str(tmp_path), "decode", ("paged_attention",),
                               mosaic=False)
    # interpreted is not compiled: on a TPU it must be a Mosaic custom call
    with pytest.raises(chip_smoke.SmokeFailure, match="interpreted"):
        chip_smoke.kernels_in_step(str(tmp_path), "decode",
                                   ("paged_attention",), mosaic=True)


def _run_smoke(cwd, env=None):
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
                       capture_output=True, text=True, timeout=300)
    results = [l for l in r.stdout.splitlines() if l.startswith("{")]
    return r, results


def test_alone_in_a_directory_it_fails_without_a_result(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    r, results = _run_smoke(tmp_path)
    assert r.returncode != 0 and not results, r.stdout + r.stderr


def test_without_a_tpu_it_fails_without_a_result():
    """No CPU mode: both legs must refuse the CPU backend, not fall back."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r, results = _run_smoke(REPO, env)
    assert r.returncode != 0 and not results, r.stdout[-2000:]
    assert "leg train FAILED" in r.stdout and "leg serve FAILED" in r.stdout
    assert "train4 not run" in r.stdout


def test_leg_result_is_the_marked_line():
    text = "noise\n" + chip_smoke.RESULT_MARK + json.dumps({"ok": True}) + "\n"
    assert chip_smoke._leg_result(text) == {"ok": True}
    assert chip_smoke._leg_result("no result here\n{}") is None
