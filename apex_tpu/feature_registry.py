"""Feature availability registry.

The reference gates each native extension behind a ``setup.py`` build flag
(``--cuda_ext``, ``--xentropy``, ... — setup.py:139-860) and guards imports at
use sites.  apex_tpu components are pure JAX and always importable; this
registry records which *backends* a component can use on the current platform
(Pallas TPU kernel vs. jnp/XLA fallback) so users and tests can introspect the
same way the reference's import guards allowed.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Feature:
    name: str
    description: str
    pallas: bool  # has a hand-written Pallas TPU kernel path
    fallback: str  # what runs when the Pallas path is unavailable


_FEATURES: dict[str, Feature] = {}


def register(name: str, description: str, pallas: bool, fallback: str = "jnp/XLA") -> None:
    _FEATURES[name] = Feature(name, description, pallas, fallback)


def available_features() -> dict[str, Feature]:
    return dict(_FEATURES)


# Core features (mirrors SURVEY.md §2 component inventory).
register("multi_tensor_apply", "packed multi-tensor scale/axpby/l2norm/opt updates", True)
register("fused_optimizers", "FusedAdam/LAMB/SGD/NovoGrad/Adagrad", True)
register("fused_layer_norm", "LayerNorm/RMSNorm fwd/bwd", True)
register("fused_dense", "GEMM+bias(+gelu) epilogues", False, "XLA fusion")
register("scaled_masked_softmax", "scaled (masked/causal) softmax", True)
register("fused_rope", "rotary position embedding (sbhd/cached/thd/2d)", True)
register("sync_batchnorm", "distributed Welford BN", False, "psum over mesh axis")
register("flash_attention", "fused multihead attention (fmha parity)", True)
register("xentropy", "fused softmax cross-entropy with label smoothing", True)
register("group_norm", "NHWC group norm (+swish)", True)
register("sparsity", "2:4 structured sparsity (ASP)", False)
register("halo_exchange", "spatial-parallel halo exchange", False, "ppermute")
register("resilience", "validated checkpointing + fault injection + guarded stepping",
         False, "host I/O + jnp")
register("supervisor", "step watchdog + heartbeat + transient retry + data guard + escalation",
         False, "host threads + I/O")
register("serving", "slotted KV-cache decode + continuous batching + "
         "exact-greedy speculative decoding + checkpoint serving",
         False, "jnp/XLA + host scheduler")
register("prefix_cache", "cross-request prefix caching: chain-hashed shared-prompt "
         "K/V reuse with bit-exact mid-prompt prefill resume",
         False, "jnp/XLA + host block store")
register("obs", "metrics registry + span tracing + Prometheus/Chrome-trace exporters",
         False, "host-side stdlib")
register("serving_slo", "request-level lifecycle traces + deterministic open-loop "
         "load generation + SLO percentile reports (TTFT/TPOT/queue-wait/goodput)",
         False, "host-side stdlib")
register("serving_policy", "serving control plane: priority classes with lossless "
         "(bit-exact) preemption, cancellation, deadline shedding, per-tenant "
         "weighted-round-robin fairness + serving chaos injection",
         False, "host scheduler + existing capture/restore/alias programs")
register("serving_tp", "tensor-parallel serving: DecodeEngine sharded over a 1-D "
         "tp mesh (Megatron column/row params, head-split KV cache, replicated "
         "tables/lengths; token-identical greedy streams, one psum pair per layer)",
         False, "shard_map over the same jitted serving programs")
register("serving_fleet", "fault-tolerant fleet serving: prefix-affinity/WRR "
         "replica router with heartbeat health states, lossless stream failover "
         "(bit-exact capture-resume or deterministic replay), rolling drain, "
         "and replica-scale chaos (kill/wedge/slow)",
         False, "host-side router over N scheduler replicas")
register("serving_quant", "quantized serving: per-channel int8 weights, "
         "per-(position, head) int8 KV cache (dense + paged), and opt-in "
         "grouped-scale int8 tp allreduce — greedy-agreement tier vs fp32, "
         "default-off byte-identical, same bounded program families",
         False, "jnp/XLA int8 inside the existing jitted serving programs")
