"""In-tree model library (the reference ships these as example images —
kubeflow/examples mnist / resnet / bert, SURVEY.md L6).

Models are flax modules with logical-axis param annotations so the same
module runs 1-device or sharded over the mesh's model/fsdp axes.
"""

from kubeflow_tpu.models.bert import (
    BertConfig,
    BertEncoder,
    BertForMaskedLM,
    BertForSequenceClassification,
)
from kubeflow_tpu.models.afmoe import AfmoeConfig, AfmoeLM
from kubeflow_tpu.models.bert_pp import BertPipelineClassifier
from kubeflow_tpu.models.deepseek_v2 import DeepseekV2Config, DeepseekV2LM
from kubeflow_tpu.models.gpt_pp import GPTPipelineLM
from kubeflow_tpu.models.granite_hybrid import GraniteHybridConfig, GraniteHybridLM
from kubeflow_tpu.models.gpt import (
    GPTConfig,
    GPTLM,
    causal_lm_eval_metrics,
    causal_lm_loss,
)
from kubeflow_tpu.models.mnist import MnistCNN, MnistMLP
from kubeflow_tpu.models.sdar_moe import (
    SdarMoeConfig,
    SdarMoeLM,
    sdar_eval_metrics,
    sdar_loss,
)
from kubeflow_tpu.models.vit import ViTClassifier, ViTConfig
from kubeflow_tpu.models.resnet import (
    ResNet,
    ResNet18,
    ResNet34,
    ResNet50,
    ResNet101,
    ResNet152,
    s2d_pack,
    stem_weights_7x7_to_s2d,
)

__all__ = [
    "AfmoeConfig",
    "AfmoeLM",
    "BertConfig",
    "BertEncoder",
    "BertForMaskedLM",
    "BertForSequenceClassification",
    "BertPipelineClassifier",
    "DeepseekV2Config",
    "DeepseekV2LM",
    "GPTConfig",
    "GPTLM",
    "causal_lm_loss",
    "causal_lm_eval_metrics",
    "MnistMLP",
    "MnistCNN",
    "GPTPipelineLM",
    "GraniteHybridConfig",
    "GraniteHybridLM",
    "ViTClassifier",
    "ViTConfig",
    "SdarMoeConfig",
    "SdarMoeLM",
    "sdar_loss",
    "sdar_eval_metrics",
    "ResNet",
    "ResNet18",
    "ResNet34",
    "ResNet50",
    "ResNet101",
    "ResNet152",
    "s2d_pack",
    "stem_weights_7x7_to_s2d",
]
