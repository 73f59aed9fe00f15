"""Smoke-run every example script (the reference keeps examples working
via nightly runs; here they are part of CI).  Each runs in its own
process on the CPU backend and must print its final 'OK' line."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# heavyweight scripts (tier-1 runs `-m 'not slow'` under a time budget;
# the PR-16 re-profile on the 1-core rig added the 8-20 s scripts below —
# their model families keep symbol/module coverage in test_model_symbols
# and ~19 faster example scripts stay in the default selection; PR 21
# brought 42 tests back to passing and added tests/test_chip_smoke.py, and
# paid for their seconds with the four slowest scripts that were left,
# 65 s together)
_SLOW = {"detection/train_ssd_toy.py", "captcha/ocr_ctc.py",
         "capsnet/capsnet_digits.py",
         "deep_embedded_clustering/dec_digits.py",
         "fcn_xs/fcn_segmentation.py",
         "detection/train_frcnn_toy.py",
         "gan/dcgan.py",
         "reinforcement_learning/dqn_gridworld.py",
         "nce_loss/nce_lm.py", "stochastic_depth/sd_digits.py",
         "vae/vae_digits.py", "time_series/lstm_forecast.py"}

EXAMPLES = [
    ("image_classification/train_mlp.py", "train_mlp example OK"),
    ("rnn/char_lm_bucketing.py", "char_lm_bucketing example OK"),
    ("long_context/ring_transformer.py", "ring_transformer example OK"),
    ("moe/switch_ffn.py", "switch_ffn example OK"),
    ("sparse/linear_classification.py",
     "sparse linear_classification example OK"),
    ("sparse/symbolic_sparse_lr.py", "symbolic_sparse_lr example OK"),
    ("model_parallel/two_stage.py", "model_parallel two_stage example OK"),
    ("profiler/profile_mlp.py", "profile_mlp example OK"),
    ("gan/dcgan.py", "dcgan example OK"),
    ("recommenders/matrix_factorization.py",
     "matrix_factorization example OK"),
    ("detection/train_ssd_toy.py", "train_ssd_toy example OK"),
    ("detection/train_frcnn_toy.py", "train_frcnn_toy example OK"),
    ("speech_recognition/train_ctc_toy.py", "train_ctc_toy example OK"),
    ("neural_style/neural_style.py", "neural_style example OK"),
    ("reinforcement_learning/dqn_gridworld.py", "dqn_gridworld example OK"),
    ("cnn_text_classification/text_cnn.py", "text_cnn example OK"),
    ("adversary/fgsm.py", "fgsm example OK"),
    ("multi_task/multi_task_digits.py", "multi_task example OK"),
    ("autoencoder/autoencoder_digits.py", "autoencoder example OK"),
    ("bi_lstm_sort/bi_lstm_sort.py", "bi_lstm_sort example OK"),
    ("svm/svm_digits.py", "svm_digits example OK"),
    ("fcn_xs/fcn_segmentation.py", "fcn_segmentation example OK"),
    ("vae/vae_digits.py", "vae example OK"),
    ("time_series/lstm_forecast.py", "lstm_forecast example OK"),
    ("nce_loss/nce_lm.py", "nce_lm example OK"),
    ("stochastic_depth/sd_digits.py", "sd_digits example OK"),
    ("bayesian_methods/sgld_regression.py", "sgld_regression example OK"),
    ("captcha/ocr_ctc.py", "ocr_ctc example OK"),
    ("deep_embedded_clustering/dec_digits.py", "dec_digits example OK"),
    ("dsd/dsd_digits.py", "dsd_digits example OK"),
    ("capsnet/capsnet_digits.py", "capsnet example OK"),
]


@pytest.mark.parametrize(
    "script,ok_line",
    [pytest.param(s, ok, marks=pytest.mark.slow) if s in _SLOW
     else (s, ok) for s, ok in EXAMPLES],
    ids=[s for s, _ in EXAMPLES])
def test_example_runs(script, ok_line):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "example", script)],
        capture_output=True, text=True, timeout=420, env=env, cwd=REPO)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert ok_line in r.stdout, r.stdout[-1000:]


def test_real_data_convergence_digits():
    """Real-pixel convergence assertion (reference
    tests/python/train/test_conv.py trains MNIST to an accuracy bar):
    the digits CLI must reach >=0.90 held-out accuracy on the bundled
    real scanned-digit dataset in a short run."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "example", "image_classification",
                      "train_digits.py"),
         "--num-epochs", "12", "--target", "0.90"],
        capture_output=True, text=True, timeout=420, env=env, cwd=REPO)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "CONVERGED" in r.stdout, r.stdout[-1000:]


def test_train_imagenet_cli(tmp_path):
    """The flagship CLI (reference example/image-classification/
    train_imagenet.py + common/fit.py): one command trains through the
    public API — model zoo symbol, ImageRecordIter (native pipeline when
    built), kvstore, Speedometer, checkpoint + resume."""
    import io as pyio

    import numpy as np
    from PIL import Image

    from mxnet_tpu import recordio

    rec = tmp_path / "train.rec"
    w = recordio.MXIndexedRecordIO(str(tmp_path / "train.idx"), str(rec),
                                   "w")
    rs = np.random.RandomState(0)
    for i in range(64):
        arr = rs.randint(0, 256, (36, 36, 3), dtype=np.uint8)
        buf = pyio.BytesIO()
        Image.fromarray(arr).save(buf, format="JPEG")
        w.write_idx(i, recordio.pack(
            recordio.IRHeader(0, float(i % 4), i, 0), buf.getvalue()))
    w.close()

    prefix = str(tmp_path / "ckpt" / "lenet")
    (tmp_path / "ckpt").mkdir()
    script = os.path.join(REPO, "example", "image_classification",
                          "train_imagenet.py")
    common = [sys.executable, script, "--data-train", str(rec),
              "--network", "lenet", "--image-shape", "3,28,28",
              "--num-classes", "4", "--num-examples", "64",
              "--batch-size", "16", "--disp-batches", "2",
              "--kv-store", "local", "--model-prefix", prefix]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(common + ["--num-epochs", "1"], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "train_imagenet OK" in r.stdout
    assert os.path.isfile(prefix + "-0001.params")
    # resume from the checkpoint
    r2 = subprocess.run(common + ["--num-epochs", "2", "--load-epoch", "1"],
                        env=env, cwd=REPO, capture_output=True, text=True,
                        timeout=420)
    assert r2.returncode == 0, r2.stdout[-2000:] + r2.stderr[-2000:]
    assert "Resumed from" in r2.stderr + r2.stdout
    assert os.path.isfile(prefix + "-0002.params")
