"""Example-family smoke tests: the fast examples must run end-to-end
and learn (exit 0) — the reference treated ``example/`` as its de-facto
integration suite (SURVEY §2 layer 11), so regressions here are product
regressions.  The slower families have dedicated tests (rcnn:
test_rcnn.py) or run standalone (ssd, gan, long-context)."""
import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_example(relpath, *args, timeout=240):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = _ROOT + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "examples", relpath),
         *args],
        capture_output=True, text=True, timeout=timeout, cwd=_ROOT,
        env=env)
    assert res.returncode == 0, \
        "%s failed:\n%s\n%s" % (relpath, res.stdout[-2000:],
                                res.stderr[-2000:])


def test_numpy_ops_example():
    _run_example("numpy-ops/numpy_softmax.py")


def test_adversary_example():
    _run_example("adversary/fgsm_toy.py")


def test_text_cnn_example():
    _run_example("cnn_text_classification/train_text_cnn_toy.py",
                 "--num-epoch", "8")


def test_autoencoder_example():
    _run_example("autoencoder/train_autoencoder_toy.py",
                 "--pretrain-epoch", "6", "--finetune-epoch", "10")


def test_neural_style_example():
    _run_example("neural-style/neural_style_toy.py")


def test_fcnxs_example():
    _run_example("fcn-xs/train_fcnxs_toy.py", "--epochs", "6")


def test_nce_loss_example():
    _run_example("nce-loss/train_nce_toy.py", "--epochs", "8")


def test_multi_task_example():
    _run_example("multi-task/train_multi_task_toy.py", "--epochs", "10")


def test_extension_ops_package():
    """Out-of-tree op package (examples/extension-ops): importing it
    registers ops with full citizenship — nd/sym surface and gradients
    through a fit() loop.  The registry entries are removed afterwards
    so the op-sweep coverage gate keeps policing only in-tree ops."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu.op import registry as _registry

    sys.path.insert(0, os.path.join(_ROOT, "examples", "extension-ops"))
    try:
        import mxtpu_contrib_ops  # noqa: F401  (registers at import)

        x = mx.nd.array([[1.0, -2.0, 0.5]])
        out = mx.nd.mish(x)
        ref = x.asnumpy() * np.tanh(np.log1p(np.exp(x.asnumpy())))
        np.testing.assert_allclose(out.asnumpy(), ref, rtol=1e-5)
        assert mx.nd.hard_swish(x).shape == x.shape
        g = mx.nd.ones((1, 3))
        np.testing.assert_allclose(
            mx.nd.rms_norm(x, g).asnumpy(),
            x.asnumpy() / np.sqrt((x.asnumpy() ** 2).mean(-1,
                                                          keepdims=True)
                                  + 1e-6), rtol=1e-5)

        # trains through Module like any in-tree op
        rng = np.random.RandomState(0)
        xs = rng.randn(128, 8).astype("f")
        w = rng.randn(8, 2).astype("f")
        ys = np.argmax(xs @ w, 1).astype("f")
        data = mx.sym.Variable("data")
        net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
        net = mx.sym.mish(net)
        net = mx.sym.FullyConnected(net, num_hidden=2, name="fc2")
        net = mx.sym.SoftmaxOutput(net, name="softmax")
        it = mx.io.NDArrayIter(xs, ys, batch_size=16)
        mod = mx.mod.Module(net, context=mx.cpu())
        mod.fit(it, num_epoch=5, optimizer="adam",
                optimizer_params={"learning_rate": 0.05},
                initializer=mx.init.Xavier())
        it.reset()
        assert mod.score(it, "acc")[0][1] > 0.9
    finally:
        sys.path.remove(os.path.join(_ROOT, "examples", "extension-ops"))
        # full cleanup: registry entries, the PEP 562 caches the nd/sym
        # __getattr__ wrote into module globals, and the module import
        # itself — so surface and registry never disagree in later tests
        for name in ("mish", "hard_swish", "rms_norm"):
            _registry._REGISTRY.pop(name, None)
            vars(mx.nd).pop(name, None)
            vars(mx.sym).pop(name, None)
        sys.modules.pop("mxtpu_contrib_ops", None)


def test_bi_lstm_sort_example():
    _run_example("bi-lstm-sort/train_sort_toy.py", "--epochs", "14")


def test_stochastic_depth_example():
    _run_example("stochastic-depth/sd_toy.py", "--epochs", "8")


def test_warpctc_example():
    _run_example("warpctc/toy_ctc.py", "--epochs", "35")


def test_svm_example():
    _run_example("svm_mnist/svm_toy.py", "--epochs", "10")


def test_matrix_factorization_example():
    _run_example("recommenders/matrix_fact_toy.py", "--epochs", "20")


def test_sgld_example():
    _run_example("bayesian-methods/sgld_toy.py", "--steps", "4000")


def test_dec_example():
    _run_example("dec/dec_toy.py", "--rounds", "40")


def test_memcost_example():
    _run_example("memcost/inception_memcost.py")


def test_module_mnist_mlp_example():
    _run_example("module/mnist_mlp.py", "--epochs", "4")


def test_module_python_loss_example():
    _run_example("module/python_loss.py", "--epochs", "6")


def test_profiler_example():
    _run_example("profiler/profiler_matmul.py")


def test_python_howto_example():
    _run_example("python-howto/howtos.py")


def test_rnn_time_major_example():
    _run_example("rnn-time-major/rnn_cell_demo.py", "--epochs", "6")


def test_kaggle_ndsb1_example():
    _run_example("kaggle-ndsb1/train_dsb_toy.py", "--epochs", "4")


def test_kaggle_ndsb2_example():
    _run_example("kaggle-ndsb2/train_heart_toy.py", "--epochs", "8")


def test_speech_demo_example():
    _run_example("speech-demo/train_acoustic_toy.py", "--epochs", "5")


def test_torch_interop_example():
    """The plugin/torch analog: a live torch.nn.Module inside the graph,
    its parameters trained by this framework's optimizer."""
    pytest.importorskip("torch")
    _run_example("torch-interop/torch_module.py")
