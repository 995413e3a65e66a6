from .conformer import (  # noqa: F401
    ConformerConfig,
    ConformerEncoder,
    ConformerForCTC,
    ConformerForRNNT,
    conformer_tiny,
)
from .ernie import (  # noqa: F401
    ErnieConfig,
    ErnieForMaskedLM,
    ErnieForSequenceClassification,
    ErnieModel,
    ernie_base,
    ernie_tiny,
)
from .falcon_h1 import FalconH1Config, FalconH1ForCausalLM, falcon_h1_tiny  # noqa: F401
from .laguna import LagunaConfig, LagunaForCausalLM, laguna_tiny  # noqa: F401
from .llama import LlamaConfig, LlamaDecoderLayer, LlamaForCausalLM, llama_7b, llama_tiny  # noqa: F401
from .whisper import (  # noqa: F401
    WhisperConfig,
    WhisperEncoder,
    WhisperForConditionalGeneration,
    whisper_tiny,
)

__all__ = [
    "LlamaConfig", "LlamaForCausalLM", "LlamaDecoderLayer", "llama_7b", "llama_tiny",
    "LagunaConfig", "LagunaForCausalLM", "laguna_tiny",
    "FalconH1Config", "FalconH1ForCausalLM", "falcon_h1_tiny",
    "ConformerConfig", "ConformerEncoder", "ConformerForCTC", "ConformerForRNNT",
    "conformer_tiny",
    "ErnieConfig", "ErnieModel", "ErnieForMaskedLM",
    "ErnieForSequenceClassification", "ernie_base", "ernie_tiny",
    "WhisperConfig", "WhisperEncoder", "WhisperForConditionalGeneration",
    "whisper_tiny",
]
