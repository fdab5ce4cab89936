"""Example scripts smoke-run end to end on CPU (reference coverage model:
example/ CI smoke runs)."""
import os
import subprocess
import sys

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _run(script, *args, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "example", script), "--cpu",
         *args],
        capture_output=True, text=True, timeout=timeout, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    return r.stdout


def test_dcgan_example():
    out = _run("dcgan.py", "--iters", "20")
    assert "DCGAN example OK" in out


def test_bi_lstm_sort_example():
    out = _run("bi_lstm_sort.py", "--steps", "60")
    assert "bi-LSTM sort example OK" in out


def test_actor_critic_example():
    out = _run("actor_critic.py", "--episodes", "25")
    assert "actor-critic example OK" in out


def test_ssd_detection_example():
    out = _run("ssd_detection.py", "--steps", "6", "--batch", "4")
    assert "ssd train: loss" in out and "detections on image 0" in out


def test_word_lm_example():
    out = _run("word_lm.py", "--steps", "50")
    assert "perplexity" in out


def test_train_mnist_example():
    out = _run("train_mnist.py", "--epochs", "1", "--limit", "128",
               "--batch-size", "32")
    assert "final accuracy" in out


def test_train_cifar_example():
    out = _run("train_cifar_resnet.py", "--epochs", "1", "--limit", "64",
               "--batch-size", "16")
    assert "epoch 0" in out


def test_bert_finetune_example():
    out = _run("bert_finetune.py", "--steps", "1", "--layers", "2",
               "--batch-size", "2", "--seq", "32", timeout=900)
    assert "step 0: loss" in out


def test_distributed_example_via_launcher():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "2", sys.executable,
         os.path.join(REPO, "example", "distributed_train.py")],
        capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "[rank 0] done" in r.stdout + r.stderr


def test_long_context_moe_example():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "example",
                                      "long_context_moe.py")],
        capture_output=True, text=True, timeout=900, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "long_context_moe OK" in r.stdout


def test_matrix_factorization_example():
    out = _run("matrix_factorization.py", "--steps", "400")
    assert "matrix factorization example OK" in out


def test_quantize_int8_example():
    out = _run("quantize_int8.py", "--iters", "120")
    assert "int8 quantization example OK" in out


def test_ocr_ctc_example():
    out = _run("ocr_ctc.py", "--iters", "60", timeout=900)
    assert "OCR CTC example OK" in out


def test_vae_example():
    out = _run("vae.py", "--iters", "120")
    assert "VAE example OK" in out
