from .attention import LatentAttention, RingAttention
from .config import LayerConfig, ModelConfig
from .layers import FeedForward, GatedFeedForward, RMSNorm
from .moe import RoutedFeedForward
from .remat import REMAT_POLICIES, resolve_remat_policy
from .ssm import Mamba2Mixer
from .transformer import RingTransformer

__all__ = [
    "RingAttention",
    "LatentAttention",
    "Mamba2Mixer",
    "FeedForward",
    "GatedFeedForward",
    "RoutedFeedForward",
    "RMSNorm",
    "LayerConfig",
    "ModelConfig",
    "RingTransformer",
    "REMAT_POLICIES",
    "resolve_remat_policy",
]
