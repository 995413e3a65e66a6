"""Flags tier, nan/inf checker, launch CLI, packaging (VERDICT item #10)."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import paddle_tpu as paddle

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestFlags:
    def test_set_get_roundtrip(self):
        assert paddle.get_flags("FLAGS_check_nan_inf") == {
            "FLAGS_check_nan_inf": False}
        paddle.set_flags({"FLAGS_check_nan_inf": True})
        try:
            assert paddle.get_flags(["FLAGS_check_nan_inf"])[
                "FLAGS_check_nan_inf"] is True
        finally:
            paddle.set_flags({"FLAGS_check_nan_inf": False})

    def test_unknown_flag_raises(self):
        with pytest.raises(ValueError, match="unknown flag"):
            paddle.set_flags({"FLAGS_not_a_flag": 1})
        with pytest.raises(ValueError, match="unknown flag"):
            paddle.get_flags("FLAGS_not_a_flag")

    def test_check_nan_inf_names_the_op(self):
        paddle.set_flags({"FLAGS_check_nan_inf": True})
        try:
            x = paddle.to_tensor(np.array([0.0, 1.0], np.float32))
            with pytest.raises(RuntimeError, match=r"op 'log'.*Inf"):
                paddle.log(x)  # log(0) = -inf
        finally:
            paddle.set_flags({"FLAGS_check_nan_inf": False})
        # disabled again: no raise
        paddle.log(paddle.to_tensor(np.array([0.0], np.float32)))


class TestLaunchCLI:
    def test_two_process_cpu_launch(self, tmp_path):
        """The CLI must lay out rank env, bootstrap jax.distributed across 2
        CPU processes, and collect both exits (reference collective
        controller behavior)."""
        script = tmp_path / "worker.py"
        script.write_text(textwrap.dedent("""
            import os
            os.environ["JAX_PLATFORMS"] = "cpu"
            import paddle_tpu as paddle
            import paddle_tpu.distributed as dist
            import jax

            env = dist.init_parallel_env()
            world = int(os.environ["PADDLE_TRAINERS_NUM"])
            rank = int(os.environ["PADDLE_TRAINER_ID"])
            assert jax.process_count() == world, jax.process_count()
            assert jax.process_index() == rank
            out = os.environ["TEST_OUT_DIR"]
            with open(os.path.join(out, f"ok.{rank}"), "w") as f:
                f.write(f"{rank}/{world}")
        """))
        out_dir = tmp_path / "out"
        out_dir.mkdir()
        env = dict(os.environ, TEST_OUT_DIR=str(out_dir), JAX_PLATFORMS="cpu",
                   PYTHONPATH=REPO)
        r = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nproc_per_node", "2", "--backend", "cpu",
             "--log_dir", str(tmp_path / "log"), str(script)],
            cwd=REPO, env=env, timeout=300, capture_output=True, text=True)
        logs = ""
        logdir = tmp_path / "log"
        if logdir.exists():
            for f in sorted(logdir.iterdir()):
                logs += f"\n--- {f.name} ---\n" + f.read_text()
        assert r.returncode == 0, f"launch failed: {r.stderr}\n{logs}"
        assert (out_dir / "ok.0").exists() and (out_dir / "ok.1").exists(), logs

    def test_failure_aborts_pod(self, tmp_path):
        script = tmp_path / "boom.py"
        script.write_text(
            "import os, sys, time\n"
            "rank = int(os.environ['PADDLE_TRAINER_ID'])\n"
            "sys.exit(3) if rank == 1 else time.sleep(60)\n")
        r = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nproc_per_node", "2", "--log_dir", str(tmp_path / "log"),
             str(script)],
            cwd=REPO, env=dict(os.environ, PYTHONPATH=REPO),
            timeout=120, capture_output=True, text=True)
        assert r.returncode == 3
        assert "rank 1 failed" in r.stderr

    def test_tpu_backend_is_one_process_per_host(self, capsys):
        """A chip belongs to one process: --backend tpu refuses a second
        worker on the node instead of letting the first take every chip,
        and it overrides an environment that would hide the device."""
        from paddle_tpu.distributed.launch.main import _parse, _worker_env

        with pytest.raises(SystemExit):
            _parse(["--backend", "tpu", "--nproc_per_node", "2", "train.py"])
        assert "one process per host" in capsys.readouterr().err
        args = _parse(["--backend", "tpu", "train.py"])
        assert os.environ.get("JAX_PLATFORMS") == "cpu"   # tests/conftest.py
        assert _worker_env(args, "127.0.0.1:1", 0)["JAX_PLATFORMS"] == "tpu"
        auto = _parse(["train.py"])
        assert _worker_env(auto, "127.0.0.1:1", 0)["JAX_PLATFORMS"] == "cpu"


class TestPackaging:
    def test_pyproject_is_installable_metadata(self):
        # cheap structural check (full pip install -e is exercised by CI
        # tooling, not unit tests): the build backend can see the package
        tomllib = pytest.importorskip(
            "tomllib", reason="tomllib is stdlib only from python 3.11")

        with open(os.path.join(REPO, "pyproject.toml"), "rb") as f:
            meta = tomllib.load(f)
        assert meta["project"]["name"] == "paddle-tpu"
        # pinned to the installed minor: the code has no branch for another
        import jax

        minor = ".".join(jax.__version__.split(".")[:2])
        assert f"jax>={minor},<0.10" in meta["project"]["dependencies"]
        assert f"jaxlib>={minor},<0.10" in meta["project"]["dependencies"]

    def test_elastic_level2_scale_down_and_resume(self, tmp_path):
        """VERDICT r2 #9 done-criterion: kill one worker -> the pod
        relaunches at the smaller world size and resumes from checkpoint
        (reference fleet/elastic/manager.py ElasticLevel 2)."""
        script = tmp_path / "elastic_worker.py"
        script.write_text(textwrap.dedent("""
            import json, os, sys
            os.environ["JAX_PLATFORMS"] = "cpu"
            import paddle_tpu as paddle
            import paddle_tpu.distributed as dist

            dist.init_parallel_env()
            world = int(os.environ["PADDLE_TRAINERS_NUM"])
            rank = int(os.environ["PADDLE_TRAINER_ID"])
            attempt = int(os.environ["PADDLE_RESTART_ATTEMPT"])
            out = os.environ["TEST_OUT_DIR"]
            ckpt = os.path.join(out, "ckpt.json")

            # checkpoint-resume: restart continues the step counter
            step = 0
            if os.path.exists(ckpt):
                with open(ckpt) as f:
                    step = json.load(f)["step"]
            # everyone reads the SAME resume step before rank 0 starts
            # writing new checkpoints (keeps the per-step barriers aligned)
            dist.barrier()

            for i in range(step, 6):
                step = i + 1
                if rank == 0:
                    with open(ckpt, "w") as f:
                        json.dump({"step": step, "world": world,
                                   "attempt": attempt}, f)
                # first incarnation: rank 1 hard-crashes mid-training
                # (os._exit: sys.exit would hang in jax.distributed's
                # atexit shutdown while rank 0 holds the barrier)
                if attempt == 0 and rank == 1 and step == 3:
                    os._exit(1)
                # lockstep: without this rank 0 could finish all steps
                # before rank 1's crash aborts the pod
                dist.barrier()
            with open(os.path.join(out, f"done.{rank}.{attempt}"), "w") as f:
                f.write(f"{world}")
        """))
        out_dir = tmp_path / "out"
        out_dir.mkdir()
        env = dict(os.environ, TEST_OUT_DIR=str(out_dir), JAX_PLATFORMS="cpu",
                   PYTHONPATH=REPO)
        r = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nproc_per_node", "2", "--backend", "cpu",
             "--max_restarts", "2", "--elastic_level", "2",
             "--min_procs", "1",
             "--log_dir", str(tmp_path / "log"), str(script)],
            cwd=REPO, env=env, timeout=300, capture_output=True, text=True)
        assert r.returncode == 0, f"{r.stderr}"
        assert "elastic scale-down: 2 -> 1 workers" in r.stderr, r.stderr
        # the relaunched (attempt 1) world has ONE worker which finished
        assert (out_dir / "done.0.1").exists()
        assert not (out_dir / "done.1.1").exists()
        import json as _json

        final = _json.load(open(out_dir / "ckpt.json"))
        assert final["world"] == 1 and final["attempt"] == 1
        # resume happened: the restarted run continued past the crash step
        assert final["step"] == 6
