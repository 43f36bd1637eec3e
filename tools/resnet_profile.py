"""ResNet-50 step-time decomposition + lever measurements (VERDICT r4 item 1).

BASELINE.json's primary vision metric (ResNet-50 imgs/sec/chip) measured
0.2622 hardware-MFU in r4 with no breakdown.  This tool gives the 59.6 ms
step the same marginal-timing treatment as the GPT flagship:

- component subtraction: full step / fwd+bwd / fwd / fwd(eval) / fwd(no-BN)
  → optimizer, backward, BN-statistics, and conv-only costs;
- levers, each an in-model number: batch size, the space-to-depth stem
  (the 3-channel 7x7 conv1 reformulated as a 12-channel 4x4 — the classic
  TPU ResNet trick: 3 input channels waste 125/128 MXU lanes), and
  bf16 vs fp32 BN statistics.

Timing protocol per the repo's measurement memory: chained async
dispatches, ONE scalar readback, per-step cost = (t(2N)-t(N))/N.

Usage: python tools/resnet_profile.py
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
from flax import linen as nn

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from apex_tpu.optimizers import FusedSGD  # noqa: E402
from apex_tpu.parallel import SyncBatchNorm  # noqa: E402


def _time_marginal(fn, state, steps_n=8):
    """fn: state -> (state, scalar). Returns (sec/step, state)."""

    def run(n, state):
        out = None
        t0 = time.perf_counter()
        for _ in range(n):
            state, out = fn(state)
        out = float(out)  # force the chain with one 4-byte readback
        return time.perf_counter() - t0, state

    _, state = run(1, state)  # compile + warmup
    t_n, state = run(steps_n, state)
    t_2n, state = run(2 * steps_n, state)
    assert t_2n > t_n, (t_n, t_2n)
    return (t_2n - t_n) / steps_n, state


class _Stem(nn.Module):
    """conv1 variants.  'std': 7x7/2 on 3 channels.  's2d': the same conv
    re-expressed over a 2x2 space-to-depth input (12 channels, 4x4/1 on a
    112x112 grid, 7x7 kernel zero-padded to 8x8 then folded) — identical
    math (up to the one-row zero pad), 4x the per-MAC input-lane density."""

    variant: str = "std"

    @nn.compact
    def __call__(self, x):
        if self.variant == "std":
            return nn.Conv(64, (7, 7), (2, 2), use_bias=False,
                           name="conv1")(x)
        b, h, w, c = x.shape
        # space-to-depth 2x2: [b,h,w,c] -> [b,h/2,w/2,4c], channel-minor
        # order (dy, dx, c) matching the folded-kernel layout below
        x = x.reshape(b, h // 2, 2, w // 2, 2, c)
        x = x.transpose(0, 1, 3, 2, 4, 5).reshape(b, h // 2, w // 2, 4 * c)
        # folded kernel param: [4,4,4c,64] — trained in this layout (a
        # std-trained 7x7 kernel could be zero-padded+folded to init it)
        return nn.Conv(64, (4, 4), (1, 1), use_bias=False, padding="SAME",
                       name="conv1_s2d")(x)


class _OnePassBN(nn.Module):
    """SyncBatchNorm's local path with ONE-pass stats: s1=sum(x),
    s2=sum(x^2) fuse into a single read of x (the flax use_fast_variance
    formulation) instead of the two dependent passes (mean, then centered
    M2) of the shipped Welford-style path.  Timing probe only — the
    shipped path keeps Welford conditioning for the cross-rank merge."""

    fuse_relu: bool = False

    @nn.compact
    def __call__(self, x, train: bool = True):
        features = x.shape[-1]
        shape = (1,) * (x.ndim - 1) + (features,)
        ra_mean = self.variable("batch_stats", "mean",
                                lambda: jnp.zeros((features,), jnp.float32))
        ra_var = self.variable("batch_stats", "var",
                               lambda: jnp.ones((features,), jnp.float32))
        if not train:
            mean, var = ra_mean.value, ra_var.value
        else:
            axes = tuple(range(x.ndim - 1))
            x32 = x.astype(jnp.float32)
            mean = jnp.mean(x32, axis=axes)
            mean2 = jnp.mean(jnp.square(x32), axis=axes)
            var = jnp.maximum(mean2 - jnp.square(mean), 0.0)
            if not self.is_initializing():
                n = float(np.prod([x.shape[a] for a in axes]))
                unbiased = var * n / max(n - 1.0, 1.0)
                ra_mean.value = 0.9 * ra_mean.value + 0.1 * mean
                ra_var.value = 0.9 * ra_var.value + 0.1 * unbiased
        scale = self.param("scale", nn.initializers.ones,
                           (features,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros,
                          (features,), jnp.float32)
        y = (x.astype(jnp.float32) - mean.reshape(shape)) * jax.lax.rsqrt(
            var.reshape(shape) + 1e-5)
        y = y * scale.reshape(shape) + bias.reshape(shape)
        if self.fuse_relu:
            y = jnp.maximum(y, 0.0)
        return y.astype(x.dtype)


class _Block(nn.Module):
    features: int
    strides: int = 1
    use_bn: bool = True
    bn_impl: str = "sync"  # 'sync' | 'flax' (one-pass E[x^2]-E[x]^2 stats)

    @nn.compact
    def __call__(self, x, train: bool = True):
        def bn(fuse_relu=False):
            if self.use_bn and self.bn_impl == "sync1p":
                m = _OnePassBN(fuse_relu=fuse_relu)
                return lambda y: m(y, train=train)
            if self.use_bn and self.bn_impl == "flax":
                # dtype=None: output stays bf16 (fp32 would poison the
                # downstream convs); param_dtype/stats fp32
                norm = nn.BatchNorm(use_running_average=not train,
                                    momentum=0.9)
                return (lambda y: nn.relu(norm(y))) if fuse_relu else norm
            if self.use_bn:
                return functools.partial(
                    SyncBatchNorm(axis_name=None, fuse_relu=fuse_relu),
                    use_running_average=not train)
            return (lambda y: nn.relu(y)) if fuse_relu else (lambda y: y)

        residual = x
        y = nn.Conv(self.features, (1, 1), use_bias=False)(x)
        y = bn(fuse_relu=True)(y)
        y = nn.Conv(self.features, (3, 3), (self.strides, self.strides),
                    use_bias=False)(y)
        y = bn(fuse_relu=True)(y)
        y = nn.Conv(self.features * 4, (1, 1), use_bias=False)(y)
        y = bn()(y)
        if residual.shape != y.shape:
            residual = nn.Conv(self.features * 4, (1, 1),
                               (self.strides, self.strides),
                               use_bias=False)(x)
            residual = bn()(residual)
        return nn.relu(y + residual)


class _ResNet50(nn.Module):
    use_bn: bool = True
    stem: str = "std"
    bn_impl: str = "sync"

    @nn.compact
    def __call__(self, x, train: bool = True):
        x = _Stem(self.stem)(x)
        if self.use_bn and self.bn_impl == "sync1p":
            x = _OnePassBN(fuse_relu=True)(x, train=train)
        elif self.use_bn and self.bn_impl == "flax":
            x = nn.relu(nn.BatchNorm(use_running_average=not train,
                                     momentum=0.9)(x))
        elif self.use_bn:
            x = SyncBatchNorm(axis_name=None, fuse_relu=True)(
                x, use_running_average=not train)
        else:
            x = nn.relu(x)
        x = nn.max_pool(x, (3, 3), (2, 2), padding="SAME")
        for i, n_blocks in enumerate((3, 4, 6, 3)):
            for j in range(n_blocks):
                x = _Block(64 * 2 ** i, strides=2 if i > 0 and j == 0 else 1,
                           use_bn=self.use_bn, bn_impl=self.bn_impl)(x, train)
        x = jnp.mean(x, axis=(1, 2))
        return nn.Dense(1000, dtype=jnp.float32)(x)


def _xent(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


def build(batch, *, use_bn=True, stem="std", bn_impl="sync"):
    model = _ResNet50(use_bn=use_bn, stem=stem, bn_impl=bn_impl)
    rng = np.random.default_rng(0)
    images = jnp.asarray(rng.standard_normal((batch, 224, 224, 3)),
                         jnp.bfloat16)
    labels = jnp.asarray(rng.integers(0, 1000, batch), jnp.int32)
    opt = FusedSGD(lr=0.1, momentum=0.9, weight_decay=1e-4)

    @jax.jit
    def init():
        variables = model.init(jax.random.PRNGKey(0),
                               images.astype(jnp.float32), train=True)
        params = variables["params"]
        stats = variables.get("batch_stats", {})
        params = jax.tree.map(
            lambda p: p.astype(jnp.bfloat16) if p.ndim >= 2 else p, params)
        return params, stats, opt.init(params)

    return model, images, labels, opt, init()


def measure(name, batch=128, steps_n=8, **build_kw):
    model, images, labels, opt, (params, stats, opt_state) = build(
        batch, **build_kw)
    has_bn = bool(stats)

    def apply_loss(p, s, train):
        kw = dict(mutable=["batch_stats"]) if (train and has_bn) else {}
        var = {"params": p, **({"batch_stats": s} if has_bn else {})}
        out = model.apply(var, images, train=train, **kw)
        if train and has_bn:
            logits, upd = out
            return _xent(logits, labels), upd.get("batch_stats", s)
        return _xent(out, labels), s

    @functools.partial(jax.jit, donate_argnums=(0,))
    def full_step(state):
        p, s, o = state

        def loss_fn(p):
            return apply_loss(p, s, True)

        (loss, new_s), grads = jax.value_and_grad(loss_fn, has_aux=True)(p)
        new_p, new_o = opt.step(grads, p, o)
        return (new_p, new_s, new_o), loss

    @functools.partial(jax.jit, donate_argnums=(0,))
    def fwd_bwd(state):
        p, s, o = state

        def loss_fn(p):
            return apply_loss(p, s, True)

        (loss, new_s), grads = jax.value_and_grad(loss_fn, has_aux=True)(p)
        # touch every grad leaf so nothing dead-code-eliminates; the global
        # reduce is ~25M adds — noise next to one conv
        gnorm = sum(jnp.sum(g.astype(jnp.float32) ** 2)
                    for g in jax.tree.leaves(grads))
        return (p, new_s, o), loss + gnorm * 1e-30

    @functools.partial(jax.jit, donate_argnums=(0,))
    def fwd_train(state):
        p, s, o = state
        loss, new_s = apply_loss(p, s, True)
        return (p, new_s, o), loss

    @functools.partial(jax.jit, donate_argnums=(0,))
    def fwd_eval(state):
        p, s, o = state
        loss, _ = apply_loss(p, s, False)
        return (p, s, o), loss

    out = {"name": name, "batch": batch}
    state = (params, stats, opt_state)
    flops = full_step.lower(state).compile().cost_analysis()["flops"]
    out["hw_flops_per_step_g"] = round(float(flops) / 1e9, 1)
    for key, fn in [("full_step", full_step), ("fwd_bwd", fwd_bwd),
                    ("fwd_train", fwd_train), ("fwd_eval", fwd_eval)]:
        sec, state = _time_marginal(fn, state, steps_n)
        out[key + "_ms"] = round(sec * 1e3, 2)
    out["imgs_per_sec"] = round(batch / (out["full_step_ms"] / 1e3), 1)
    out["mfu_hw"] = round(float(flops) / (out["full_step_ms"] / 1e3)
                          / 1e12 / 197.0, 4)
    print(json.dumps(out))
    return out


def main():
    which = sys.argv[1:] or ["components", "batch", "stem", "nobn"]
    if "components" in which:
        measure("baseline_b128", batch=128)
    if "batch" in which:
        for b in (64, 256):
            measure(f"batch_{b}", batch=b)
    if "stem" in which:
        measure("s2d_stem_b128", batch=128, stem="s2d")
    if "nobn" in which:
        # conv-only skeleton: BN replaced by (fused) relu/identity — the
        # difference vs baseline is the total BN cost (stats+normalize+bwd)
        measure("no_bn_b128", batch=128, use_bn=False)


if __name__ == "__main__":
    main()
