"""paddle_tpu.nn (reference: python/paddle/nn/__init__.py)."""
from . import functional  # noqa: F401
from . import initializer  # noqa: F401
from .clip import ClipGradByGlobalNorm, ClipGradByNorm, ClipGradByValue  # noqa: F401
from .layer import Layer, ParamAttr  # noqa: F401
from .layers.activation import *  # noqa: F401,F403
from .layers.common import *  # noqa: F401,F403
from .layers.container import *  # noqa: F401,F403
from .layers.conv import (  # noqa: F401
    Conv1D,
    Conv1DTranspose,
    Conv2D,
    Conv2DTranspose,
    Conv3D,
    Conv3DTranspose,
)
from .layers.loss import *  # noqa: F401,F403
from .layers.norm import *  # noqa: F401,F403
from .layers.pooling import *  # noqa: F401,F403
from .layers.rnn import *  # noqa: F401,F403
from .layers.transformer import *  # noqa: F401,F403
# module-shaped aliases (reference: paddle.nn.common / .loss / ... are
# importable module names as well as the flat layer namespace)
from .layers import common, container, loss, norm, pooling, rnn, vision  # noqa: F401,E402
from .layers import conv  # noqa: F401,E402


# the latent-attention / routed-expert layers resolve LAZILY: no program
# that does not name them pays their import
_LATENT = ("RMSNorm", "GatedMLP", "LatentAttention", "RoutedExperts")
_DSA = ("IndexedAttention",)
_SSM = ("Mamba2Mixer", "GroupedAttention")


def __getattr__(name):
    if name in _LATENT:
        from .layers import latent

        return getattr(latent, name)
    if name in _DSA:
        from .layers import dsa

        return getattr(dsa, name)
    if name in _SSM:
        from .layers import ssm

        return getattr(ssm, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
