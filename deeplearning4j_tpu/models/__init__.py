"""Model zoo: canonical configs for the benchmark/parity suite.

The reference era has no in-tree model zoo (its examples repo served that
role); these builders produce the BASELINE.md configs:

  #1 LeNet-5 (MNIST, sequential)            — lenet()
  #2 ResNet-50 (ImageNet-class, DAG)        — resnet50() / resnet()
  #3 GravesLSTM char-RNN                    — char_rnn_lstm()
"""

from .lenet import lenet
from .resnet import resnet, resnet50, resnet_tiny
from .char_rnn import char_rnn_lstm
from .classic import alexnet, deep_autoencoder, vgg16
from .transformer import draft_transformer_lm, generate, transformer_lm
from .nemotron_h import nemotron_h_lm
from .pangu import pangu_ultra_moe_lm
from .evabyte import evabyte_lm
from .solar_open2 import solar_open2_lm

__all__ = ["lenet", "resnet", "resnet50", "resnet_tiny", "char_rnn_lstm",
           "alexnet", "vgg16", "deep_autoencoder", "transformer_lm",
           "draft_transformer_lm", "generate", "nemotron_h_lm",
           "pangu_ultra_moe_lm", "evabyte_lm", "solar_open2_lm"]
