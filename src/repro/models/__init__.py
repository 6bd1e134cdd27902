# Model zoo: unified config + decoder-only LM (dense/moe/mla/ssm/hybrid) and
# encoder-decoder (whisper).  Pure-function APIs over param pytrees.
from . import encdec, layers, lm
from .config import MLAConfig, ModelConfig, MoEConfig, SSMConfig, YarnScaling

__all__ = [
    "encdec",
    "layers",
    "lm",
    "ModelConfig",
    "MoEConfig",
    "SSMConfig",
    "MLAConfig",
    "YarnScaling",
]
