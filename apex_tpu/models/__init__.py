"""apex_tpu.models — flagship model families built on the kernel toolbox.

The reference ships its model zoo through examples and the transformer
testing package (GPT/BERT, SURVEY.md §2.3); BASELINE.md's target table
additionally names the Llama-2 family (TP x PP, RMSNorm + rope + fused
optimizers).  This package holds the production-shaped model definitions:

- :mod:`apex_tpu.models.llama` — Llama-2/3-class causal LM: RMSNorm,
  rotary embeddings, SwiGLU, grouped-query attention, tensor-parallel
  sharding, flash attention, fused LM-head loss.
- :mod:`apex_tpu.models.vit` — Vision Transformer classifier (patch
  embedding, pre-LN encoder over the tp layers, fused LN kernels).
- :mod:`apex_tpu.models.nemotron_h` — Nemotron-H hybrid decoder, serving
  only: Mamba-2, latent routed experts and rope-free GQA by a pattern
  string, one mixer a layer.
- :mod:`apex_tpu.models.dots3` — dots3-note decoder, serving only: latent
  attention in every layer (a learned top-k key selector on the full
  layers, a window on the others, a gate a head) and sigmoid-routed gated
  experts beside a shared expert.
- :mod:`apex_tpu.models.mellum` — Mellum decoder, serving only:
  grouped-query attention under a window of K/V rows on the
  ``sliding_attention`` layers and at full extent under YaRN on the
  ``full_attention`` ones, softmax-routed gated experts in every layer.
"""

from apex_tpu.models.dots3 import Dots3NoteConfig, Dots3NoteForCausalLM

from apex_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from apex_tpu.models.llama_pipeline import (
    LlamaPipeConfig,
    build_llama_pipeline,
    init_llama_pipeline_params,
    make_llama_3d_train_step,
)
from apex_tpu.models.mellum import MellumConfig, MellumForCausalLM
from apex_tpu.models.nemotron_h import NemotronHConfig, NemotronHForCausalLM
from apex_tpu.models.vit import ViTConfig, ViTForImageClassification

__all__ = ["Dots3NoteConfig", "Dots3NoteForCausalLM", "LlamaConfig", "LlamaForCausalLM", "LlamaPipeConfig",
           "build_llama_pipeline", "init_llama_pipeline_params",
           "make_llama_3d_train_step", "MellumConfig", "MellumForCausalLM",
           "NemotronHConfig",
           "NemotronHForCausalLM", "ViTConfig", "ViTForImageClassification"]
