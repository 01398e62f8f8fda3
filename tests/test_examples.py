"""Example smoke tests, file 1 of 3 (reference: tests/python/train —
small end-to-end runs gating convergence). Each example asserts its own
learning criterion and exits nonzero on failure; tests run them as a
user would. The examples are dealt round-robin over three files so that
--dist loadfile runs them on three workers. The ones that take minutes
each on the CPU are marked `slow` (left out of the tier-1 run, which has
to end inside its time limit).
"""
import pytest

from example_runner import run_example


@pytest.mark.slow
def test_dcgan():
    out = run_example("gan/dcgan.py", "--steps", "12",
                      "--batch-size", "8")
    assert "final loss_D" in out


def test_matrix_factorization_sharded():
    out = run_example("recommenders/matrix_factorization.py",
                      "--epochs", "3", "--obs", "4096", "--sharded")
    assert "final mse" in out


@pytest.mark.slow
def test_profiler_example(tmp_path):
    out = run_example("profiler_demo/profile_resnet.py", "--steps", "2",
                      "--output", str(tmp_path / "trace"))
    assert "trace written" in out


@pytest.mark.slow
def test_gluon_cifar10_example():
    out = run_example("gluon/train_cifar10.py", "--epochs", "2")
    assert "epoch 0" in out


@pytest.mark.slow
def test_neural_style():
    out = run_example("neural_style/neural_style.py", "--steps", "45")
    assert "final loss" in out


def test_nce_toy():
    out = run_example("nce-loss/toy_nce.py", "--epochs", "8",
                      "--train-size", "4096")
    assert "NCE_OK" in out


@pytest.mark.slow
def test_vae():
    out = run_example("vae/vae_mnist.py", "--epochs", "8")
    assert "VAE_OK" in out


def test_multivariate_forecast():
    out = run_example("multivariate_time_series/lstnet_forecast.py",
                      "--epochs", "6", "--train-size", "2048")
    assert "FORECAST_OK" in out


@pytest.mark.slow
def test_stochastic_depth():
    out = run_example("stochastic-depth/sd_resnet.py", "--epochs", "6",
                      "--train-size", "2000")
    assert "STOCHASTIC_DEPTH_OK" in out


def test_wgan_gradient_penalty():
    out = run_example("gradient_penalty/wgan_gp.py", "--steps", "120")
    assert "WGAN_GP_OK" in out


def test_dsd_training():
    out = run_example("dsd/dsd_train.py", "--epochs-per-phase", "3")
    assert "DSD_OK" in out


def test_memcost_remat():
    out = run_example("memcost/memory_cost.py")
    assert "MEMCOST_OK" in out
