from baton_tpu.models.linear import linear_regression_model
from baton_tpu.models.mlp import mlp_classifier_model
from baton_tpu.models.cnn import cnn_mnist_model
from baton_tpu.models.resnet import resnet_model, resnet18_cifar_model
from baton_tpu.models.lora import lora_wrap, lora_trainable, merge_lora
from baton_tpu.models.bert import BertConfig, bert_classifier_model
from baton_tpu.models.llama import (
    LlamaConfig,
    decoder_lora_model,
    llama_lm_model,
    llama_lora_target,
    projection_lora_target,
)
from baton_tpu.models.lstm import LSTMConfig, lstm_lm_model
from baton_tpu.models.moe import MoEConfig, moe_apply, moe_init
from baton_tpu.models.transformer import CCAConfig, IndexerConfig, MLAConfig
from baton_tpu.models.vit import ViTConfig, vit_model

__all__ = [
    "linear_regression_model",
    "mlp_classifier_model",
    "cnn_mnist_model",
    "resnet_model",
    "resnet18_cifar_model",
    "lora_wrap",
    "lora_trainable",
    "merge_lora",
    "BertConfig",
    "bert_classifier_model",
    "LlamaConfig",
    "llama_lm_model",
    "llama_lora_target",
    # the hybrid decoder (layer_types: gated delta-rule layers beside full
    # attention) as a frozen base under LoRA on every projection
    "decoder_lora_model",
    "projection_lora_target",
    "LSTMConfig",
    "lstm_lm_model",
    # the expert layer that holds some of its router's experts, and the
    # sizes of latent and of compressed convolutional attention
    # (LlamaConfig.moe, LlamaConfig.mla, LlamaConfig.cca)
    "MoEConfig",
    "moe_apply",
    "moe_init",
    "CCAConfig",
    "IndexerConfig",
    "MLAConfig",
    "ViTConfig",
    "vit_model",
]
