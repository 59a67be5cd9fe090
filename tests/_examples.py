"""Appendix-C example workloads, round 5 batch (ref:
example/gluon/*, example/multi-task, example/nce-loss,
example/model-parallel-lstm, example/speech_recognition,
example/vae, example/recommenders, example/memcost).

Each example asserts its own convergence/behavior gate in --quick
mode; ``run_quick`` runs one exactly as a user would — a fresh
``python examples/<name>.py --quick`` subprocess on the 8-virtual-
device CPU mesh (MXTPU_FORCE_CPU).  Subprocess isolation is load-
bearing, not style: accumulating a dozen example workloads' compiled
programs in one process segfaulted XLA:CPU's compiler on the CTC
scan-transpose (deterministically, only after ~8 prior tests), and a
fresh interpreter per workload is also the honest way to test a
script-shaped artifact.

The table is cut into the three ``tests/test_examples_gluon*.py``:
the driver's ``--dist loadfile`` hands a whole file to one worker, so
no file may hold one for more than a fifth of the run.
"""
import json
import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_EXAMPLES = os.path.join(_REPO, "examples")

QUICK = [
    "word_language_model",
    "model_parallel_lstm",
    "memcost",
    "nce_loss",
    "matrix_factorization",
    "multi_task",
    "vae",
    "cnn_text_classification",
    "speech_ctc",
    "dcgan",
    "actor_critic",
    "adversary_fgsm",
    "fcn_segmentation",
    "svm_mnist",
    "bi_lstm_sort",
    "stochastic_depth",
    "profiler_demo",
    "captcha_crnn",
    "neural_style",
]


def run_quick(name):
    env = dict(os.environ)
    env["MXTPU_FORCE_CPU"] = "1"
    env.pop("JAX_PLATFORMS", None)
    r = subprocess.run(
        [sys.executable, os.path.join(_EXAMPLES, f"{name}.py"),
         "--quick"],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=_REPO)
    assert r.returncode == 0, (
        f"{name} --quick failed (rc={r.returncode})\n"
        f"stdout tail: {r.stdout[-1500:]}\n"
        f"stderr tail: {r.stderr[-1500:]}")
    # the last stdout line is the example's JSON summary
    last = [l for l in r.stdout.strip().splitlines()
            if l.startswith("{")][-1]
    json.loads(last)   # parseable summary contract
