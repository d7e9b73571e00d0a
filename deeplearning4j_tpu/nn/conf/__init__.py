"""Configuration DSL: serializable layer/network configs with shape inference.

Parity target: reference ``nn/conf/`` (NeuralNetConfiguration.Builder,
MultiLayerConfiguration, ComputationGraphConfiguration, layer configs,
InputType-driven nIn inference and automatic preprocessor insertion).
"""

from .inputs import InputType
from .builders import NeuralNetConfiguration, ListBuilder
from .multi_layer import MultiLayerConfiguration
from . import attention as _attention  # noqa: F401  (serde registration)
from . import mla as _mla  # noqa: F401  (serde registration)
from . import eva as _eva  # noqa: F401  (serde registration)
from . import kda as _kda  # noqa: F401  (serde registration)
from . import moe as _moe  # noqa: F401  (serde registration)
from . import ssm as _ssm  # noqa: F401  (serde registration)

__all__ = [
    "InputType", "NeuralNetConfiguration", "ListBuilder", "MultiLayerConfiguration",
]
