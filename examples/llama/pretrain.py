"""Llama pretraining driver — the BASELINE.md "Llama-2 7B" recipe in
miniature: RMSNorm + rope + GQA + SwiGLU over tensor parallelism, fused
multi-tensor Adam.

TPU shape: a 2-D ``Mesh(("dp", "tp"))``; parameters shard over tp via
``shard_map`` (column/row layouts exactly as the model's parallel layers
expect, optimizer m/v sharded like their parameters), the batch shards
over dp, grads pmean over dp, and the model's vocab-parallel CE computes
the loss with psums under tp.  Synthetic next-token data (zero egress).

    python examples/llama/pretrain.py [--tp 2] [--layers 4] [--steps 10]

Parameters and optimizer state are created *inside* a jitted init whose
``out_shardings`` are the tp layouts, so every device holds only its shard
from the first allocation (an eager ``model.init`` would materialize the
whole fp32 model and its Adam state on device 0 first — 13 GB at
Llama-1B width), and the step donates both.  ``--bf16`` is the
``bench.py`` llama-1b recipe: bf16 matmul weights and bf16 Adam moments.
Per-device ``memory_stats()`` are printed after init and after the last
step where the backend reports them.

``--pp N`` switches to the full 3-D dp × pp × tp layout (BASELINE.md
row 5: "Llama-2 7B, TP x PP"): the decoder is sliced into pipeline stages
(:mod:`apex_tpu.models.llama_pipeline`) and driven by the true-1F1B
schedule; embed/head grads psum over pp, block grads stay per-stage:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
    python examples/llama/pretrain.py --tp 2 --pp 2 --micro-batch 2
"""

from __future__ import annotations

import argparse
import functools

import jax
import jax.numpy as jnp
import numpy as np
from apex_tpu.utils.compat import NO_REP_CHECK, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from apex_tpu.models import LlamaConfig, LlamaForCausalLM
from apex_tpu.optimizers import FusedAdam
from apex_tpu.optimizers.fused_adam import AdamState


def param_specs(params):
    """tp shardings for the Llama parameter tree."""

    def spec(path, leaf):
        del leaf
        name = "/".join(str(p.key) for p in path if hasattr(p, "key"))
        if "embed_tokens" in name or name.endswith("lm_head"):
            return P("tp", None)
        if any(k in name for k in ("q_proj", "k_proj", "v_proj",
                                   "gate_proj", "up_proj")):
            return P(None, "tp")
        if any(k in name for k in ("o_proj", "down_proj")):
            return P("tp", None)
        return P()  # norms replicated

    return jax.tree_util.tree_map_with_path(spec, params)


def opt_specs(pspecs):
    """FusedAdam state is (AdamState(step, m, v), MasterState): m/v shard
    like their parameters, step and the (absent) master copy replicate."""
    return (AdamState(P(), pspecs, pspecs), P())


def device_memory(devices) -> dict:
    """``{device id: (bytes_in_use, peak_bytes_in_use)}`` for the devices
    whose backend reports memory (the CPU backend does not)."""
    out = {}
    for d in devices:
        stats = d.memory_stats()
        if stats:
            out[d.id] = (stats.get("bytes_in_use"),
                         stats.get("peak_bytes_in_use"))
    return out


def _print_memory(when: str, devices) -> None:
    mem = device_memory(devices)
    if not mem:
        print(f"memory {when}: not reported by this backend")
    for dev_id, (in_use, peak) in sorted(mem.items()):
        print(f"memory {when}: device {dev_id} in_use "
              f"{in_use / 2**30:.2f} GiB peak {peak / 2**30:.2f} GiB")


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--hidden", type=int, default=128)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--kv-heads", type=int, default=2)
    ap.add_argument("--ffn", type=int, default=352)
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=None,
                    help="global batch (2-D path; default 8). With --pp > 1 "
                    "the global batch is micro-batch * dp * n-micro — "
                    "passing --batch there is an error, not silently ignored")
    ap.add_argument("--tp", type=int, default=2)
    ap.add_argument("--pp", type=int, default=1,
                    help="pipeline stages; > 1 uses the 1F1B schedule over "
                    "a dp x pp x tp mesh")
    ap.add_argument("--micro-batch", type=int, default=2,
                    help="per-dp-rank microbatch size (pp > 1 only)")
    ap.add_argument("--n-micro", type=int, default=4,
                    help="microbatches per step (pp > 1 only)")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--bf16", action="store_true",
                    help="bf16 matmul weights + bf16 Adam moments (2-D "
                    "path; the bench.py llama-1b recipe)")
    return ap.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    if args.pp > 1:
        if args.batch is not None:
            raise SystemExit(
                "--batch applies to the 2-D path only; with --pp the "
                "global batch is --micro-batch * dp * --n-micro")
        if args.bf16:
            raise SystemExit("--bf16 applies to the 2-D path only")
        return main_3d(args)
    _, first, last = train_2d(args)
    return last


def train_2d(args):
    """The dp x tp run; returns ``(params, first_loss, last_loss)`` with
    ``params`` the trained global arrays, tp-sharded over the mesh."""
    if args.batch is None:
        args.batch = 8
    devices = jax.devices()
    if len(devices) % args.tp:
        raise SystemExit(f"device count {len(devices)} must be a multiple "
                         f"of --tp {args.tp}")
    dp = len(devices) // args.tp
    if args.batch % dp:
        raise SystemExit(f"--batch {args.batch} must be a multiple of "
                         f"dp={dp}")
    mesh = Mesh(np.array(devices).reshape(dp, args.tp), ("dp", "tp"))

    cfg = LlamaConfig(
        vocab_size=args.vocab, hidden_size=args.hidden,
        intermediate_size=args.ffn, num_hidden_layers=args.layers,
        num_attention_heads=args.heads, num_key_value_heads=args.kv_heads,
        max_position_embeddings=args.seq)
    model = LlamaForCausalLM(cfg)
    opt = FusedAdam(lr=args.lr, state_dtype=(jnp.bfloat16 if args.bf16
                                             else jnp.float32))
    rng = np.random.default_rng(args.seed)

    # one fixed batch: fresh uniform-random batches have nothing learnable
    # beyond the unigram floor, so convergence is asserted by memorization
    batch0 = jnp.asarray(
        rng.integers(0, args.vocab, (args.batch, args.seq)), jnp.int32)

    def init_fn(ids):
        params = model.init(jax.random.PRNGKey(args.seed), ids)
        if args.bf16:
            params = jax.tree.map(
                lambda p: p.astype(jnp.bfloat16) if p.ndim >= 2 else p,
                params)
        return params, opt.init(params)

    pspecs = param_specs(jax.eval_shape(init_fn, batch0)[0])
    ospecs = opt_specs(pspecs)

    def train_step(params, opt_state, ids):
        labels = jnp.roll(ids, -1, axis=1)

        def loss_fn(p):
            return model.apply(p, ids, labels=labels).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        grads = jax.tree.map(lambda g: jax.lax.pmean(g, "dp"), grads)
        loss = jax.lax.pmean(loss, "dp")
        new_params, new_state = opt.step(grads, params, opt_state)
        return new_params, new_state, loss

    with mesh:
        # sharded from the first allocation: the init program's outputs
        # land tp-split, so no device ever holds the whole model
        params, opt_state = jax.jit(
            init_fn, out_shardings=jax.tree.map(
                lambda spec: NamedSharding(mesh, spec), (pspecs, ospecs),
                is_leaf=lambda x: isinstance(x, P)))(batch0)
        _print_memory("after init", devices)
        step = jax.jit(shard_map(
            train_step, mesh=mesh,
            in_specs=(pspecs, ospecs, P("dp")),
            out_specs=(pspecs, ospecs, P()),
            **NO_REP_CHECK), donate_argnums=(0, 1))
        first = last = None
        for it in range(args.steps):
            params, opt_state, loss = step(params, opt_state, batch0)
            loss = float(loss)
            first = loss if first is None else first
            last = loss
            if it % 2 == 0 or it == args.steps - 1:
                print(f"step {it:3d}  loss {loss:.4f}  dp={dp} tp={args.tp}")
        _print_memory("after last step", devices)

    assert np.isfinite(last) and last < first, (first, last)
    print(f"llama pretrain OK: dp={dp} tp={args.tp}, "
          f"loss {first:.4f} -> {last:.4f}")
    return params, first, last


def main_3d(args):
    """dp × pp × tp with the 1F1B schedule (BASELINE.md row 5 layout)."""
    from apex_tpu.models import LlamaPipeConfig, make_llama_3d_train_step
    from apex_tpu.transformer import parallel_state
    from apex_tpu.transformer.pipeline_parallel import (
        forward_backward_pipelining_1f1b,
    )

    devices = jax.devices()
    world = args.tp * args.pp
    if len(devices) % world:
        raise SystemExit(f"device count {len(devices)} must be a multiple "
                         f"of tp*pp={world}")
    dp = len(devices) // world
    if args.layers % args.pp:
        raise SystemExit(f"--layers {args.layers} must divide by "
                         f"--pp {args.pp}")
    mesh = parallel_state.initialize_model_parallel(
        args.tp, args.pp, devices=devices)

    cfg = LlamaConfig(
        vocab_size=args.vocab, hidden_size=args.hidden,
        intermediate_size=args.ffn, num_hidden_layers=args.layers,
        num_attention_heads=args.heads, num_key_value_heads=args.kv_heads,
        max_position_embeddings=args.seq)
    pcfg = LlamaPipeConfig(
        config=cfg, layers_per_stage=args.layers // args.pp,
        sequence_parallel_enabled=args.tp > 1)
    opt = FusedAdam(lr=args.lr)
    init_fn, train_step = make_llama_3d_train_step(
        pcfg, opt, forward_backward_pipelining_1f1b)

    rng = np.random.default_rng(args.seed)
    ids = rng.integers(0, args.vocab,
                       (args.n_micro, args.micro_batch * dp, args.seq))
    batches = {"ids": jnp.asarray(ids, jnp.int32),
               "labels": jnp.asarray(np.roll(ids, -1, axis=-1), jnp.int32)}
    batch_specs = {"ids": P(None, "dp"), "labels": P(None, "dp")}

    with mesh:
        params, opt_state = jax.jit(shard_map(
            functools.partial(init_fn, jax.random.PRNGKey(args.seed)),
            mesh=mesh, in_specs=(batch_specs,), out_specs=P(),
            **NO_REP_CHECK))(batches)
        step = jax.jit(shard_map(
            train_step, mesh=mesh, in_specs=(P(), P(), batch_specs),
            out_specs=(P(), P(), P()), **NO_REP_CHECK))
        first = last = None
        for it in range(args.steps):
            params, opt_state, loss = step(params, opt_state, batches)
            last = float(loss)
            first = last if first is None else first
            if it % 2 == 0 or it == args.steps - 1:
                print(f"step {it:3d}  loss {last:.4f}  "
                      f"dp={dp} pp={args.pp} tp={args.tp}")
    parallel_state.destroy_model_parallel()

    assert np.isfinite(last) and last < first, (first, last)
    print(f"llama pretrain OK: dp={dp} pp={args.pp} tp={args.tp}, "
          f"loss {first:.4f} -> {last:.4f}")
    return last


if __name__ == "__main__":
    main()
