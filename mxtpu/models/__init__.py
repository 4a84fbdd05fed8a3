"""mxtpu.models — flagship model families, TPU-first functional cores.

The reference shipped its model breadth through
``python/mxnet/gluon/model_zoo/`` (CNNs) and the GluonNLP ecosystem
[path cite — unverified]. The rebuild keeps a Gluon model_zoo for API
parity and, in addition, provides functional cores here: pure
``forward(cfg, params, ...)`` functions over parameter pytrees that
compose directly with ``mxtpu.parallel`` (sharding rules, jitted train
step, remat, scan-over-layers) — the idiomatic shape for pjit/XLA.
"""
from . import bert
from . import blockdiff_moe
from . import latent_moe
from . import llama
from . import resnet
from . import retention
from . import sambay
from .bert import BertConfig
from .blockdiff_moe import BlockDiffMoEConfig
from .latent_moe import LatentMoEConfig
from .llama import LlamaConfig
from .resnet import ResNetConfig
from .retention import RetentionConfig
from .sambay import SambaYConfig

__all__ = ["llama", "resnet", "sambay", "latent_moe", "retention",
           "blockdiff_moe",
           "LlamaConfig", "ResNetConfig", "SambaYConfig", "LatentMoEConfig",
           "RetentionConfig", "BlockDiffMoEConfig",
           "SERVING_FAMILIES", "serving_family"]

# the families ``serve.ServeEngine`` can be given, by the ``family`` of
# their config class; each module has llama.py's serving surface
SERVING_FAMILIES = {"llama": llama, "sambay": sambay,
                    "latent_moe": latent_moe, "retention": retention,
                    "blockdiff_moe": blockdiff_moe}


def serving_family(cfg):
    """The module whose programs serve ``cfg``."""
    return SERVING_FAMILIES[cfg.family]
