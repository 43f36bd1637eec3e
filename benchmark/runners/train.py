"""Training runner: one donated, jitted step — the benchmark's own copy of
``bench.build_training`` (bench.py:2278-2340) over ``GPTModel`` and
``FusedLAMB`` — fed a fresh batch per step, loss read back each step."""

from __future__ import annotations

import functools
import time

import numpy as np

SPANS = ("make_batch", "step", "readback")


def build(config: dict):
    """``(model, init_all, train_step)`` for a GPT-2 config file."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.optimizers import FusedLAMB
    from apex_tpu.transformer.testing import GPTModel

    model = GPTModel(
        num_layers=config["n_layer"], hidden_size=config["n_embd"],
        num_attention_heads=config["n_head"],
        vocab_size=config["assumed"]["padded_vocab_size"],
        max_sequence_length=config["n_positions"],
        params_dtype=jnp.float32)
    opt = FusedLAMB(lr=1e-3, state_dtype=jnp.bfloat16)
    wdtype = jnp.dtype(config["assumed"]["weights_dtype"])

    # init + cast (bf16 matrices, fp32 norms and biases; fp32 masters live
    # inside the optimizer) + optimizer state in ONE jitted program.  The
    # key is an argument: a seed baked into the program would compile a
    # new one for every seed (58 s where the cached one loads in 13)
    @jax.jit
    def init_all(key, ids):
        params = model.init(key, ids)
        params = jax.tree.map(
            lambda p: p.astype(wdtype)
            if p.dtype == jnp.float32 and p.ndim >= 2 else p, params)
        return params, opt.init(params)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, opt_state, ids, labels):
        def loss_fn(p):
            return model.apply(p, ids, labels=labels).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        new_params, new_state = opt.step(grads, params, opt_state)
        return new_params, new_state, loss

    return model, init_all, train_step


def check_against_reference(model, params, ids, labels, tolerance):
    """Per-token losses of the system on one sequence against the plain
    float32 reference on the same weights: the error's norm over the norm
    of the reference's variation about its mean.  (The mean loss itself
    sits at ln(vocab) for any near-random model, so it could not tell a
    wrong model from a right one.)"""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import gpt2

    sys_loss = jax.jit(lambda p, i, l: model.apply(p, i, labels=l))(
        params, ids, labels)[0].astype(jnp.float32)
    ref_loss = gpt2.token_losses(params, ids[0], labels[0],
                                 n_head=model.num_attention_heads)
    sys_loss, ref_loss = np.asarray(sys_loss), np.asarray(ref_loss)
    err = float(np.linalg.norm(sys_loss - ref_loss)
                / np.linalg.norm(ref_loss - ref_loss.mean()))
    return {"reference_rel_err": err, "reference_tolerance": tolerance,
            "reference_ok": bool(err <= tolerance)}


def run(ctx) -> dict:
    import jax

    from apex_tpu import _logging

    config, traffic = ctx.config, ctx.traffic
    batch, seq = traffic["batch"], traffic["seq_len"]
    rng = np.random.default_rng(ctx.seed)
    dispatch: list = []

    def sink(event):
        if event.get("event") == "kernel_dispatch":
            dispatch.append((event.get("op"), event.get("path")))

    def make_batch():
        ids = rng.integers(0, config["vocab_size"], (batch, seq),
                           dtype=np.int32)
        return (jax.device_put(ids),
                jax.device_put(np.roll(ids, -1, axis=1)))

    _logging.add_event_sink(sink)
    try:
        model, init_all, train_step = build(config)
        ids, labels = make_batch()
        params, opt_state = init_all(
            jax.random.PRNGKey(ctx.seed % (2 ** 31 - 1)), ids)
        jax.block_until_ready(params)
        ctx.setup.mark("init")
        lowered = train_step.lower(params, opt_state, ids, labels)
        ctx.setup.mark("trace_lower")
        step = lowered.compile()
        ctx.setup.mark("compile_or_cache_load")
    finally:
        _logging.remove_event_sink(sink)
    for _ in range(traffic["warmup_steps"]):
        params, opt_state, loss = step(params, opt_state, ids, labels)
        ids, labels = make_batch()
        float(loss)
    ctx.setup.mark("warmup")

    # Every step's loss is read back, one step late: step k+1 is
    # dispatched before the host waits for loss k, as a trainer that logs
    # without stalling the chip does.  (Waiting for loss k first left the
    # device idle 4 ms a step — 1 % — and made the rate follow the host's
    # latency, which differs by 2.4 ms a step between processes: PERF.md.)
    tracer = ctx.tracer(SPANS)
    losses, ends = [], []
    in_flight = None
    ctx.setup.window_opens()
    t_open = time.perf_counter()
    tracer.window_opens(t_open)
    with ctx.compiles.window():
        while True:
            with tracer.span("step"):
                params, opt_state, loss = step(params, opt_state, ids,
                                               labels)
            with tracer.span("make_batch"):
                ids, labels = make_batch()
            if in_flight is not None:
                with tracer.span("readback"):
                    losses.append(float(in_flight))
                ends.append(time.perf_counter())
                tracer.poll(ends[-1])
            in_flight = loss
            if ends and ends[-1] - t_open >= ctx.seconds:
                break
        losses.append(float(in_flight))
        ends.append(time.perf_counter())
    tracer.stop()
    device = ctx.device_report()

    n = len(losses)
    step_ms = [1e3 * (b - a) for a, b in zip([t_open] + ends, ends)]
    finite = [bool(np.isfinite(x)) for x in losses]
    quarter = max(n // 4, 1)
    checks = {
        "losses_finite": all(finite),
        "loss_falling": bool(np.mean(losses[-quarter:])
                             < np.mean(losses[:quarter])),
        "kernels_on_pallas_path": bool(dispatch) and all(
            path == "pallas" for _, path in dispatch),
        "no_compile_in_window": not ctx.compiles.events,
    }
    checks.update(check_against_reference(
        model, params, ids[:1], labels[:1], traffic["reference_tolerance"]))
    ctx.setup.mark("checks")
    ok = all(v for k, v in checks.items() if isinstance(v, bool))
    return {
        "correct": ok, "attempted": n, "failed": finite.count(False),
        "end_to_end": {
            # every step is whole: the first is dispatched as the window
            # opens, the last one's loss is waited for
            "train_tok_s": n * batch * seq / (ends[-1] - t_open)},
        "counters": {"steps": n},
        "tracer": tracer, "device": device,
        "notes": {"checks": checks, "loss_first": losses[0],
                  "loss_last": losses[-1], "steps": n,
                  # a stall shows as one long step, a slow chip as all
                  "host_step_ms": {"median": float(np.median(step_ms)),
                                   "max": max(step_ms),
                                   "argmax": int(np.argmax(step_ms))},
                  "reference_call_sites": sorted(
                      {op for op, path in dispatch if path != "pallas"}),
                  "compiles_in_window": ctx.compiles.events},
    }
