"""Serving runner for an open loop on the Llama family: requests arrive at
the traffic file's ``rate_per_s`` whether or not earlier ones have finished
(``lib/open_loop.py``), through ``lib/serving.py``'s window and checks, on
``runners/serve.py``'s model, weights and reference check.

Cells: ``mistral-7b-l16.chat-open`` (and the tests'
``tiny-mistral.tiny-chat-open``).  Counters beside ``lib/serving.py``'s:
``queue_wait_p90_ms`` and ``ttft_from_arrival_p90_ms``, both timed from an
arrival's scheduled time; every run's notes carry the generator's
lateness.
"""

from __future__ import annotations

from benchmark.lib import open_loop
from benchmark.lib.serving import serve_cell
from benchmark.runners.serve import (
    build_model,
    check_against_reference,
    make_params,
)


def run(ctx) -> dict:
    import jax

    config, traffic = ctx.config, ctx.traffic
    if traffic["loop"] != "open":
        raise ValueError(f"traffic loop {traffic['loop']!r}: this runner "
                         f"drives open loops")
    model = build_model(config)
    params = make_params(model, config, ctx.seed)
    jax.block_until_ready(params)
    ctx.setup.mark("init")
    return serve_cell(
        ctx, model, params,
        lambda engine: check_against_reference(engine, config, traffic,
                                               ctx.seed),
        drive=open_loop.drive)
