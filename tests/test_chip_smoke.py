"""chip_smoke.py's CPU rehearsal, and the dispatch rules it stands on.

The smoke itself only proves anything on the chip; what tier-1 can hold
is that the script still runs end to end (the rehearsal drives the same
phases through the same entry points at a tiny size), that it refuses to
run on the CPU unless asked to rehearse, and that no probe on its path
turns a broken or misconfigured device into a quiet reference run.
"""

import json
import os
import resource
import subprocess
import sys
import types
from pathlib import Path

import pytest

from apex_tpu import _logging
from apex_tpu.ops import _dispatch

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))
import chip_smoke  # noqa: E402


def _smoke(tmp_path, *flags):
    env = {**os.environ, "PYTHONPATH": str(REPO),
           # the env var wins over the checkout default: nothing this
           # test compiles lands in <checkout>/.jax_cache
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    env.pop("APEX_TPU_KERNELS", None)
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"),
         "--out", str(tmp_path / "out"), *flags],
        capture_output=True, text=True, env=env, timeout=600)


def test_rehearsal_runs_every_phase_and_is_stamped(tmp_path):
    out = _smoke(tmp_path, "--rehearse")
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-4000:]
    lines = [json.loads(ln) for ln in out.stdout.splitlines()
             if ln.startswith("{")]
    phases = [ln["phase"] for ln in lines if "phase" in ln]
    assert phases == ["device", "kernels", "train", "hand-off", "serve"]
    assert all(ln["passed"] for ln in lines if "phase" in ln)
    # every kernel call site took the kernel, none a reference - but the
    # K/V reads of the decode step and of a prompt chunk, which at the
    # card's 64-wide heads are the reference by their shape predicates
    for ln in lines:
        for key in ln.get("kernel_dispatch", {}):
            assert key.endswith(":pallas") or (ln["phase"], key) in (
                ("serve", "cached_decode_attention:reference"),
                ("serve", "kv_chunk_attention:reference")), ln
    by = {ln["phase"]: ln for ln in lines if "phase" in ln}
    assert {k.split(":")[0] for k in by["train"]["kernel_dispatch"]} == {
        "flash_attention", "norm_fwd", "norm_bwd", "fused_lm_head"}
    assert by["train"]["losses"][-1] < by["train"]["losses"][0]
    assert by["serve"]["decode_compiles"] == 1
    assert {"cached_decode_attention:reference",
            "kv_chunk_attention:reference"} <= set(
                by["serve"]["kernel_dispatch"])
    assert by["device"]["compile_cache_dir"] == str(tmp_path / "cache")
    # no file-size limit here: the params went over as ONE checkpoint
    assert by["hand-off"]["checkpoints"] == 1
    assert by["hand-off"]["in_memory_leaves"] == []
    # the last stdout line is the result, stamped as a CPU rehearsal
    assert json.loads(out.stdout.splitlines()[-1]) == {
        "ok": True, "rehearsal": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    # the hand-off checkpoint is gone, the record is kept
    assert sorted(os.listdir(tmp_path / "out")) == ["result.json"]


def test_without_a_chip_the_smoke_fails_and_prints_no_result(tmp_path):
    out = _smoke(tmp_path)          # no --rehearse: the CPU is not enough
    assert out.returncode != 0
    assert "needs 'tpu'" in out.stderr
    assert not [ln for ln in out.stdout.splitlines() if '"ok"' in ln]


def _hand_off(tmp_path, soft_limit):
    """``phase_handoff`` over a small tree with the process's file-size
    limit lowered for the duration (python ignores SIGXFSZ: a write past
    the limit raises EFBIG, as on the machine that refused PR 21)."""
    import jax.numpy as jnp

    kib = 1024 // 2                       # bf16 elements per KiB
    params = {"params": {
        "embed": jnp.arange(256 * kib, dtype=jnp.float32).astype(
            jnp.bfloat16).reshape(256, kib),            # 256 KiB
        **{f"layers_{i}": {"kernel": jnp.full((32, kib), i, jnp.bfloat16)}
           for i in range(6)}}}                         # 32 KiB each
    sm = chip_smoke.Smoke(types.SimpleNamespace(chips=1), {},
                          str(tmp_path / "out"))
    sm.params, sm.trained_steps = params, 3
    old = resource.getrlimit(resource.RLIMIT_FSIZE)
    resource.setrlimit(resource.RLIMIT_FSIZE, (soft_limit, old[1]))
    try:
        obs = chip_smoke.phase_handoff(sm)
    finally:
        resource.setrlimit(resource.RLIMIT_FSIZE, old)
    assert sm.params is not params
    assert not os.path.exists(tmp_path / "out" / "ckpt")
    return obs


def test_hand_off_obeys_the_file_size_limit(tmp_path):
    """448 KiB of params under a 100 KiB file limit: the kernels go over
    three to a checkpoint, the 256 KiB leaf in memory, and the phase line
    says so (``phase_handoff`` itself checks the tree arrived equal)."""
    obs = _hand_off(tmp_path, 100 * 1024)
    assert obs["file_bound"]["rlimit_fsize"] == obs["file_bound"]["bound"]
    assert obs["checkpoints"] == 2
    assert obs["largest_file_bytes"] == 96 * 1024
    assert obs["bytes"] == 192 * 1024
    assert obs["in_memory_leaves"] == ["['params']['embed']"]
    assert obs["in_memory_bytes"] == 256 * 1024
    assert obs["refused_writes"] == []


def test_hand_off_halves_its_bound_when_a_write_is_refused(
        tmp_path, monkeypatch):
    """A limit the script cannot read beforehand (a filesystem's own) shows
    as EFBIG from the write: the bound halves until the files fit."""
    monkeypatch.setattr(
        chip_smoke, "_file_bound",
        lambda out: {"rlimit_fsize": None, "disk_free": 2**40,
                     "bound": 2**39})
    obs = _hand_off(tmp_path, 100 * 1024)
    assert [r["bytes"] for r in obs["refused_writes"]] == [
        448 * 1024, 192 * 1024]
    assert obs["checkpoints"] == 2
    assert obs["in_memory_leaves"] == ["['params']['embed']"]


def test_hand_off_that_can_checkpoint_nothing_fails(tmp_path):
    with pytest.raises(RuntimeError, match="no leaf of the params fits"):
        _hand_off(tmp_path, 1024)


def test_on_tpu_propagates_a_backend_init_error(monkeypatch):
    """A backend that fails to start must not read as "not a TPU": that
    answer sends the whole model to the jnp references."""
    def boom():
        raise RuntimeError("Unable to initialize backend 'tpu': chip held")

    _dispatch.on_tpu.cache_clear()
    monkeypatch.setattr(_dispatch.jax, "default_backend", boom)
    monkeypatch.delenv("APEX_TPU_KERNELS", raising=False)
    try:
        with pytest.raises(RuntimeError, match="chip held"):
            _dispatch.on_tpu()
        with pytest.raises(RuntimeError, match="chip held"):
            _dispatch.kernels_enabled()
    finally:
        _dispatch.on_tpu.cache_clear()


def test_interpret_mode_on_a_tpu_backend_is_an_error(monkeypatch):
    _dispatch.on_tpu.cache_clear()
    monkeypatch.setattr(_dispatch.jax, "default_backend", lambda: "tpu")
    monkeypatch.setenv("APEX_TPU_KERNELS", "interpret")
    try:
        with pytest.raises(RuntimeError, match="interpret on a TPU"):
            _dispatch.use_interpret()
        with pytest.raises(RuntimeError, match="interpret on a TPU"):
            _dispatch.kernels_enabled()
    finally:
        _dispatch.on_tpu.cache_clear()


def test_shape_fallback_is_reported_when_kernels_are_enabled(monkeypatch):
    """With kernels on, a call site whose shape predicate fails says so;
    with kernels off there is no decision and no event."""
    import jax.numpy as jnp

    from apex_tpu.ops.flash_attention import flash_attention

    seen = []

    def sink(event):
        if event["event"] == "kernel_dispatch":
            seen.append((event["op"], event["path"], event["d"]))

    _logging.add_event_sink(sink)
    try:
        q = jnp.ones((1, 2, 16, 32))         # head dim 32: no kernel
        monkeypatch.delenv("APEX_TPU_KERNELS", raising=False)
        flash_attention(q, q, q, causal=True)
        assert seen == []
        monkeypatch.setenv("APEX_TPU_KERNELS", "interpret")
        flash_attention(q, q, q, causal=True)
        assert seen == [("flash_attention", "reference", 32)]
        q = jnp.ones((1, 2, 16, 64))
        flash_attention(q, q, q, causal=True)
        assert seen[-1] == ("flash_attention", "pallas", 64)
    finally:
        _logging.remove_event_sink(sink)
