"""Example smoke tests, file 3 of 3 (reference: tests/python/train —
small end-to-end runs gating convergence). Each example asserts its own
learning criterion and exits nonzero on failure; tests run them as a
user would. The examples are dealt round-robin over three files so that
--dist loadfile runs them on three workers. The ones that take minutes
each on the CPU are marked `slow` (left out of the tier-1 run, which has
to end inside its time limit).
"""
import pytest

from example_runner import run_example


def test_matrix_factorization():
    out = run_example("recommenders/matrix_factorization.py",
                      "--epochs", "3", "--obs", "4096")
    assert "final mse" in out


def test_custom_softmax_numpy_op():
    out = run_example("numpy_ops/custom_softmax.py", "--epochs", "2")
    assert "final train accuracy" in out


def test_sharded_resnet_example():
    out = run_example("parallel/sharded_resnet.py", "--steps", "2")
    assert "params synced" in out


@pytest.mark.slow
def test_cnn_text_classification():
    out = run_example("cnn_text_classification/train_cnn_text.py",
                      "--epochs", "4", "--n", "1024")
    assert "final test-acc" in out


@pytest.mark.slow
def test_ctc_lstm_ocr():
    # loss-only: full decode convergence takes ~6 min on a 1-core VM
    # (the example's default config reaches 100% exact-sequence acc);
    # the smoke asserts the loss collapse phase
    out = run_example("ctc/lstm_ocr.py", "--epochs", "5",
                      "--train-size", "256", "--loss-only",
                      timeout=540)
    assert "CTC_OCR_OK" in out


def test_bi_lstm_sort():
    out = run_example("bi-lstm-sort/sort_lstm.py", "--epochs", "8",
                      "--train-size", "2048", "--threshold", "0.75")
    assert "BI_LSTM_SORT_OK" in out


def test_svm_classifier():
    out = run_example("svm_mnist/svm_classifier.py", "--epochs", "8")
    assert "SVM_OK" in out


@pytest.mark.slow
def test_fgsm_adversary():
    out = run_example("adversary/fgsm.py", "--epochs", "5")
    assert "FGSM_OK" in out


@pytest.mark.slow
def test_capsnet():
    out = run_example("capsnet/capsnet.py", "--epochs", "4",
                      "--train-size", "1500", timeout=540)
    assert "CAPSNET_OK" in out


def test_mnist_module_fit():
    out = run_example("image_classification/train_mnist.py",
                      "--epochs", "8")
    assert "MNIST_EXAMPLE_OK" in out


def test_gradcam_visualization():
    out = run_example("cnn_visualization/gradcam.py", "--epochs", "5")
    assert "GRADCAM_OK" in out
