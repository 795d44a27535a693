"""The examples/ scripts stay runnable (subprocess smoke, CPU mesh, tiny steps).

The scripts themselves don't force a platform (on a TPU machine they use the
chip); here each runs under a bootstrap that pins the 8-device CPU platform
before the script body imports jax — same trick as tests/model/workload_env.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BOOTSTRAP = (
    "import sys, runpy;"
    f"sys.path.insert(0, {os.path.join(REPO, 'tests', 'model')!r});"
    "from workload_env import setup; setup();"
    "sys.argv = [sys.argv[1]] + sys.argv[2:];"
    "runpy.run_path(sys.argv[0], run_name='__main__')"
)


def _run_example(script, *args, timeout=600):
    r = subprocess.run(
        [sys.executable, "-c", BOOTSTRAP, os.path.join(REPO, "examples", script),
         *args],
        capture_output=True, text=True, timeout=timeout, cwd=REPO)
    assert r.returncode == 0, f"{script} failed:\n{r.stdout[-2000:]}\n{r.stderr[-2000:]}"
    return r.stdout


@pytest.mark.parametrize("extra", [
    (), ("--zero", "3", "--sparse", "--seq", "128")])
def test_train_gpt2_example(extra):
    out = _run_example("train_gpt2.py", "--steps", "3", "--layers", "2",
                       "--width", "64", "--vocab", "512", *extra)
    assert "greedy continuation:" in out


def test_train_bert_mlm_example():
    out = _run_example("train_bert_mlm.py", "--steps", "3", "--layers", "1",
                       "--hidden", "64", "--vocab", "256")
    assert "mlm loss" in out


def test_generate_text_example():
    out = _run_example("generate_text.py", "--new-tokens", "6", "--beams", "2")
    assert "greedy :" in out and "beam-2" in out
