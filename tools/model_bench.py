"""Measured numbers for the non-GPT BASELINE.md target-table rows.

BASELINE.json's primary metric names **ResNet-50 imgs/sec/chip** next to
the GPT rows; the r1-r3 record only ever measured GPT.  This tool runs
the other three target-table configurations on the real chip with the
same protocol as bench.py (scalar readback forces the chain,
per-step cost is the marginal (t(2N)-t(N))/N):

- ``resnet50``  — BASELINE row 1: O2-style bf16 + SyncBatchNorm(1 chip) +
  FusedSGD momentum (the examples/imagenet stack).
- ``vit-l16``   — BASELINE row 4 component set on one chip: ViT-L/16 +
  FusedAdam, bf16 weights.
- ``bert-large``— BASELINE row 2: BERT-large (24x1024, s512) masked-LM +
  binary head, FusedLAMB, fused LN + flash attention.

FLOPs come from XLA's own cost analysis of the compiled training step
(``compiled.cost_analysis()['flops']``) — no hand-derived constants —
so ``mfu_hw`` is hardware-FLOPs utilization of the device's bf16 peak
(``bench._PEAK_TFLOPS``, keyed by ``device_kind``; unknown device = error).

Usage: python tools/model_bench.py [resnet50 vit-l16 bert-large]
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(REPO, "examples", "imagenet"), REPO):
    if _p not in sys.path:  # idempotent: bench.py imports this module too
        sys.path.insert(0, _p)


def _marginal_time(step, state, steps_n):
    """(state, per-step seconds) via the t(2N)-t(N) protocol."""

    def run(n, state):
        loss = None
        t0 = time.perf_counter()
        for _ in range(n):
            state, loss = step(state)
        loss = float(loss)  # scalar readback forces the chain
        return time.perf_counter() - t0, loss, state

    _, loss0, state = run(1, state)          # compile + warmup
    assert np.isfinite(loss0), loss0
    t_n, _, state = run(steps_n, state)
    t_2n, loss_end, state = run(2 * steps_n, state)
    assert t_2n > t_n * 1.2, (t_n, t_2n)
    return state, (t_2n - t_n) / steps_n, loss0, loss_end


QUIET = False  # bench.py sets True when embedding results in its own lines


def _report(name, batch, step_s, flops_per_step, unit_per_step, unit):
    import bench  # the one peak table; an unknown device_kind raises

    per_sec = unit_per_step / step_s
    tflops = flops_per_step / step_s / 1e12
    dev = jax.devices()[0]
    mfu_hw = round(tflops / bench._peak_tflops(dev), 4)
    assert 0.0 < mfu_hw <= 1.0, f"measured hw-MFU {mfu_hw} is not physical"
    out = {
        "metric": f"{name}_{unit}_per_sec_per_chip",
        "value": round(per_sec, 1),
        "unit": f"{unit}/s/chip",
        "step_time_ms": round(step_s * 1e3, 2),
        "batch": batch,
        "model_tflops_per_sec": round(tflops, 2),
        "mfu_hw": mfu_hw,
        "flops_source": "xla_cost_analysis",
    }
    if not QUIET:
        print(json.dumps(out))
    return out


def bench_resnet50(batch=128, steps_n=8):
    from main import cross_entropy, resnet50  # examples/imagenet/main.py

    from apex_tpu.optimizers import FusedSGD

    model = resnet50(num_classes=1000, axis_name=None)
    rng = np.random.default_rng(0)
    images = jnp.asarray(rng.standard_normal((batch, 224, 224, 3)),
                         jnp.bfloat16)
    labels = jnp.asarray(rng.integers(0, 1000, batch), jnp.int32)
    opt = FusedSGD(lr=0.1, momentum=0.9, weight_decay=1e-4)

    @jax.jit
    def init():
        variables = model.init(jax.random.PRNGKey(0), images.astype(
            jnp.float32), train=True)
        params, stats = variables["params"], variables["batch_stats"]
        # O2-style: conv/dense kernels bf16, BN params fp32
        params = jax.tree.map(
            lambda p: p.astype(jnp.bfloat16) if p.ndim >= 2 else p, params)
        return params, stats, opt.init(params)

    params, stats, opt_state = init()

    @functools.partial(jax.jit, donate_argnums=(0,))
    def train_step(state):
        params, stats, opt_state = state

        def loss_fn(p):
            logits, upd = model.apply(
                {"params": p, "batch_stats": stats},
                images.astype(jnp.bfloat16), train=True,
                mutable=["batch_stats"])
            return cross_entropy(logits, labels), upd

        (loss, upd), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        new_params, new_opt = opt.step(grads, params, opt_state)
        return (new_params, upd["batch_stats"], new_opt), loss

    flops = train_step.lower(
        (params, stats, opt_state)).compile().cost_analysis()["flops"]
    state, step_s, l0, le = _marginal_time(
        train_step, (params, stats, opt_state), steps_n)
    assert le < l0, (l0, le)
    return _report("resnet50", batch, step_s, flops, batch, "imgs")


def bench_vit_l16(batch=64, steps_n=8):
    from apex_tpu.models import ViTConfig, ViTForImageClassification
    from apex_tpu.optimizers import FusedAdam

    cfg = ViTConfig.vit_l16()
    model = ViTForImageClassification(cfg)
    rng = np.random.default_rng(0)
    pixels = jnp.asarray(rng.standard_normal((batch, 224, 224, 3)),
                         jnp.bfloat16)
    labels = jnp.asarray(rng.integers(0, cfg.num_labels, batch), jnp.int32)
    opt = FusedAdam(lr=3e-4, weight_decay=0.05)

    @jax.jit
    def init():
        params = model.init(jax.random.PRNGKey(0),
                            pixels.astype(jnp.float32))
        params = jax.tree.map(
            lambda p: p.astype(jnp.bfloat16) if p.ndim >= 2 else p, params)
        return params, opt.init(params)

    params, opt_state = init()

    @functools.partial(jax.jit, donate_argnums=(0,))
    def train_step(state):
        params, opt_state = state

        def loss_fn(p):
            logits = model.apply(p, pixels.astype(jnp.bfloat16))
            logp = jax.nn.log_softmax(logits.astype(jnp.float32))
            return -jnp.mean(jnp.take_along_axis(
                logp, labels[:, None], axis=1))

        loss, grads = jax.value_and_grad(loss_fn)(params)
        new_params, new_opt = opt.step(grads, params, opt_state)
        return (new_params, new_opt), loss

    flops = train_step.lower(
        (params, opt_state)).compile().cost_analysis()["flops"]
    state, step_s, l0, le = _marginal_time(
        train_step, (params, opt_state), steps_n)
    assert le < l0, (l0, le)
    return _report("vit_l16", batch, step_s, flops, batch, "imgs")


def bench_bert_large(batch=16, seq=512, steps_n=8):
    """Real BERT pretraining objective (the row's component set): 15%
    masked-LM loss over masked positions only, + the binary NSP head, +
    ~10% tail padding driving the pad-mask/segment path of flash
    attention."""
    from apex_tpu.optimizers import FusedLAMB
    from apex_tpu.transformer.testing.standalone_bert import BertModel

    vocab, mask_id = 30592, 103
    model = BertModel(num_layers=24, hidden_size=1024,
                      num_attention_heads=16, vocab_size=vocab,
                      max_sequence_length=seq, params_dtype=jnp.float32)
    rng = np.random.default_rng(0)
    orig = rng.integers(0, vocab, (batch, seq))
    mlm_mask = rng.random((batch, seq)) < 0.15
    ids_np = np.where(mlm_mask, mask_id, orig)
    lengths = rng.integers(int(seq * 0.9), seq + 1, batch)
    attn_mask = (np.arange(seq)[None, :] < lengths[:, None])
    mlm_mask &= attn_mask                      # no loss on padding
    ids = jnp.asarray(ids_np, jnp.int32)
    lm_labels = jnp.asarray(orig, jnp.int32)
    loss_w = jnp.asarray(mlm_mask, jnp.float32)
    attention_mask = jnp.asarray(attn_mask, jnp.int32)
    nsp_labels = jnp.asarray(rng.integers(0, 2, batch), jnp.int32)
    opt = FusedLAMB(lr=1e-3, state_dtype=jnp.bfloat16)

    @jax.jit
    def init():
        params = model.init(jax.random.PRNGKey(0), ids)
        params = jax.tree.map(
            lambda p: p.astype(jnp.bfloat16)
            if p.dtype == jnp.float32 and p.ndim >= 2 else p, params)
        return params, opt.init(params)

    params, opt_state = init()

    @functools.partial(jax.jit, donate_argnums=(0,))
    def train_step(state):
        params, opt_state = state

        def loss_fn(p):
            per_tok, binary = model.apply(
                p, ids, attention_mask=attention_mask, lm_labels=lm_labels)
            mlm = jnp.sum(per_tok * loss_w) / jnp.sum(loss_w)
            logp = jax.nn.log_softmax(binary.astype(jnp.float32))
            nsp = -jnp.mean(jnp.take_along_axis(
                logp, nsp_labels[:, None], axis=1))
            return mlm + nsp

        loss, grads = jax.value_and_grad(loss_fn)(params)
        new_params, new_opt = opt.step(grads, params, opt_state)
        return (new_params, new_opt), loss

    flops = train_step.lower(
        (params, opt_state)).compile().cost_analysis()["flops"]
    state, step_s, l0, le = _marginal_time(
        train_step, (params, opt_state), steps_n)
    assert le < l0, (l0, le)
    return _report("bert_large", batch, step_s, flops, batch * seq, "tokens")


BENCHES = {"resnet50": bench_resnet50, "vit-l16": bench_vit_l16,
           "bert-large": bench_bert_large}


def main():
    names = sys.argv[1:] or list(BENCHES)
    for name in names:
        BENCHES[name]()


if __name__ == "__main__":
    main()
