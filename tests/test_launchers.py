"""Launcher integration: the production train/serve entrypoints run
end-to-end in --smoke mode, including checkpoint-resume across invocations
(the restart path of fault tolerance)."""
import os
import subprocess
import sys
import tempfile

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the launchers place JAX's compile cache; point it away from the repo
ENV = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
           JAX_COMPILATION_CACHE_DIR=tempfile.mkdtemp(
               prefix="jax-cache-test-"))


def _run(args, timeout=600):
    r = subprocess.run([sys.executable, "-m"] + args, capture_output=True,
                       text=True, env=ENV, timeout=timeout, cwd=REPO)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


@pytest.mark.slow
def test_train_launcher_smoke_and_resume():
    ckpt = tempfile.mkdtemp()
    out = _run(["repro.launch.train", "--arch", "qwen2-0.5b", "--smoke",
                "--steps", "6", "--ckpt-every", "3", "--ckpt", ckpt])
    assert "[train] done" in out
    # resume: a second invocation picks up from the checkpoint
    out = _run(["repro.launch.train", "--arch", "qwen2-0.5b", "--smoke",
                "--steps", "8", "--ckpt-every", "4", "--ckpt", ckpt])
    assert "resumed at step 6" in out


@pytest.mark.slow
def test_serve_launcher_smoke():
    out = _run(["repro.launch.serve", "--arch", "mamba2-780m", "--smoke",
                "--requests", "3", "--slots", "2", "--max-new", "4"])
    assert "3 requests" in out


@pytest.mark.slow
def test_dryrun_single_cell_small():
    """dryrun lowers+compiles on the production mesh from a clean process
    (uses the cached cell if present; --force would recompile)."""
    out = _run(["repro.launch.dryrun", "--arch", "qwen2-0.5b",
                "--shape", "decode_32k", "--mesh", "single"],
               timeout=1200)
    assert "[ok]" in out or "[skip-cached]" in out
