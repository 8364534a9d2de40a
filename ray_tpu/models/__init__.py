"""Model zoo (flax): eight LM families, ResNets, MLP, NatureCNN.

The LM families, each a file and a ``model_kind`` of
``serve/llm_engine.py::build_model``: GPT-2 (``gpt2.py``), the Llama decoder
with its layer options, OLMoE's among them (``llama.py``), Falcon-H1
(``falcon_h1.py``), Nemotron-H (``nemotron_h.py``), Ling-linear
(``ling_linear.py``), GLM-DSA (``glm_dsa.py``), Latent-MoE
(``latent_moe.py``: Sarvam-105B's shape) and the EVA decoder
(``eva_decoder.py``: EvaByte's shape, EVA attention in every layer).  The
first four are imported here; the last four are imported by ``build_model``
alone, so that no other kind's set-up pays for them.

The reference's model layer is RLlib's ModelCatalog + torch/tf ModelV2
(rllib/models/catalog.py, rllib/models/torch/*) plus whatever user code
brings to Train.  Here models are flax modules designed for pjit: static
shapes, bfloat16-friendly, logical sharding annotations exposed per model
via `param_logical_axes`.
"""
from ray_tpu.models.gpt2 import (  # noqa: F401
    GPT2,
    GPT2Config,
    GPT2Stage,
    GPT2WithValue,
    gpt2_loss_fn,
    split_stages,
)
from ray_tpu.models.llama import (  # noqa: F401
    Llama,
    LlamaConfig,
    LlamaStage,
    llama_loss_fn,
)
from ray_tpu.models.falcon_h1 import FalconH1, FalconH1Config  # noqa: F401
from ray_tpu.models.nemotron_h import NemotronH, NemotronHConfig  # noqa: F401
from ray_tpu.models.resnet import ResNet, ResNetConfig  # noqa: F401
from ray_tpu.models.mlp import MLP  # noqa: F401
from ray_tpu.models.nature_cnn import NatureCNN  # noqa: F401
