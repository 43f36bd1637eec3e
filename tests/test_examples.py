"""Smoke tests for the example drivers added in r3 (dcgan, bert).

Each runs the real script in a subprocess on the virtual CPU mesh — the
same way a user would — and checks its own convergence assertions pass.
"""

import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent

# extend (not replace) the environment: a from-scratch dict hardcodes
# HOME/PATH and drops TMPDIR/proxies for non-root users.  PYTHONPATH
# points the subprocess at this checkout.
ENV = {**os.environ,
       "JAX_PLATFORMS": "cpu",
       "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
       "PYTHONPATH": str(REPO)}


@pytest.mark.slow   # ~21 s: two-optimizer fp16 scaling keeps tier-1
# witnesses in test_amp.py; the dcgan driver itself is smoke-only
def test_dcgan_amp_two_optimizers():
    out = subprocess.run(
        [sys.executable, str(REPO / "examples" / "dcgan" / "main_amp.py"),
         "--steps", "4", "--batch", "8", "--half", "fp16",
         "--opt-level", "O2"],
        capture_output=True, text=True, timeout=600, env=ENV)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "dcgan amp OK" in out.stdout


def test_bert_pretrain_dp():
    out = subprocess.run(
        [sys.executable, str(REPO / "examples" / "bert" / "pretrain.py"),
         "--steps", "6", "--layers", "2", "--hidden", "64", "--heads", "2",
         "--vocab", "256", "--seq", "64", "--batch", "8"],
        capture_output=True, text=True, timeout=600, env=ENV)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "bert pretrain OK: dp=8" in out.stdout


@pytest.mark.slow   # ~11 s: tier-1 keeps test_llama_pretrain_3d_tp_pp_dp,
# which drives the same pretrain.py with tp AND pp AND dp axes live — the
# 2-D tp×dp mesh is a strict subset of that witness
def test_llama_pretrain_tp_dp():
    out = subprocess.run(
        [sys.executable, str(REPO / "examples" / "llama" / "pretrain.py"),
         "--steps", "6", "--layers", "2", "--hidden", "64", "--heads", "4",
         "--kv-heads", "2", "--ffn", "128", "--vocab", "256", "--seq", "64",
         "--batch", "8", "--tp", "2"],
        capture_output=True, text=True, timeout=600, env=ENV)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "llama pretrain OK: dp=4 tp=2" in out.stdout


def _make_fake_imagefolder(root, classes=3, per_class=6, size=40):
    from PIL import Image
    rng = __import__("numpy").random.default_rng(0)
    for c in range(classes):
        d = root / f"class_{c}"
        d.mkdir(parents=True)
        for i in range(per_class):
            arr = rng.integers(0, 255, (size, size, 3), dtype="uint8")
            Image.fromarray(arr).save(d / f"img_{i}.jpg")


@pytest.mark.slow   # ~13 s: the data-path machinery itself (ImageFolder,
# PIL decode, augment, batching, worker pool) keeps its in-process tier-1
# witnesses (test_batch_iterator_workers_matches_serial,
# test_prefetch_loader_propagates_decode_errors); this subprocess rider
# re-proves only the example's --data-dir flag wiring
def test_imagenet_real_data_path(tmp_path):
    """--data-dir trains on a real image tree (VERDICT r3 item 8): PIL
    decode + augment + prefetch feeding the amp/DDP/FusedSGD step."""
    _make_fake_imagefolder(tmp_path / "train")
    out = subprocess.run(
        [sys.executable, str(REPO / "examples" / "imagenet" / "main.py"),
         "--arch", "resnet10", "--image-size", "32", "--batch-size", "8",
         "--steps", "6", "--data-dir", str(tmp_path / "train")],
        capture_output=True, text=True, timeout=600, env=ENV)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "data: 18 images, 3 classes" in out.stdout
    assert "OK" in out.stdout


def test_prefetch_loader_propagates_decode_errors(tmp_path):
    """A corrupt image must surface as the decode error itself, not as a
    bare StopIteration indistinguishable from clean end-of-data."""
    import pytest

    sys.path.insert(0, str(REPO / "examples" / "imagenet"))
    from data import ImageFolder, PrefetchLoader, batch_iterator

    _make_fake_imagefolder(tmp_path / "t", classes=2, per_class=3)
    (tmp_path / "t" / "class_0" / "img_0.jpg").write_bytes(b"not a jpeg")
    ds = ImageFolder(str(tmp_path / "t"))
    loader = PrefetchLoader(batch_iterator(ds, 6, 32, train=False, epochs=1))
    with pytest.raises(Exception) as ei:
        for _ in range(10):
            next(loader)
    assert not isinstance(ei.value, StopIteration), (
        "decode failure was swallowed into end-of-data")


def test_llama_pretrain_3d_tp_pp_dp():
    """BASELINE.md row 5 component set: Llama over dp x pp x tp with the
    1F1B schedule (VERDICT r3 item 5)."""
    out = subprocess.run(
        [sys.executable, str(REPO / "examples" / "llama" / "pretrain.py"),
         "--steps", "6", "--layers", "4", "--hidden", "64", "--heads", "4",
         "--kv-heads", "2", "--ffn", "128", "--vocab", "256", "--seq", "32",
         "--tp", "2", "--pp", "2", "--micro-batch", "2", "--n-micro", "4"],
        capture_output=True, text=True, timeout=600, env=ENV)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "llama pretrain OK: dp=2 pp=2 tp=2" in out.stdout


def test_batch_iterator_workers_matches_serial(tmp_path):
    """workers>0 fans decode across a thread pool (the reference
    DataLoader's workers knob, PERF_NOTES r5 input-pipeline section):
    same batch shapes/labels and the same images modulo augmentation
    randomness; eval mode (deterministic) must match exactly."""
    import numpy as np

    sys.path.insert(0, str(REPO / "examples" / "imagenet"))
    from data import ImageFolder, batch_iterator

    _make_fake_imagefolder(tmp_path / "t", classes=2, per_class=4)
    ds = ImageFolder(str(tmp_path / "t"))
    serial = list(batch_iterator(ds, 4, 32, train=False, epochs=1))
    pooled = list(batch_iterator(ds, 4, 32, train=False, epochs=1,
                                 workers=4))
    assert len(serial) == len(pooled) == 2
    for (si, sl), (pi, pl) in zip(serial, pooled):
        np.testing.assert_array_equal(sl, pl)
        np.testing.assert_allclose(si, pi, rtol=1e-6)
    # train mode with workers: just shape/dtype sanity (augmentation rng
    # streams differ from the serial path by design)
    imgs, labels = next(batch_iterator(ds, 4, 32, train=True, workers=2))
    assert imgs.shape == (4, 32, 32, 3) and labels.shape == (4,)
