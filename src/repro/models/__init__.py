"""Model zoo: one composable decoder implementation, ten architectures."""

from .config import (LayerSpec, MLASpec, ModelConfig, MoESpec, SSMSpec,
                     layout_groups)
from .transformer import (decode_step, forward, init_caches, init_model,
                          prefill, train_loss, train_loss_and_stats)

__all__ = [
    "LayerSpec", "MLASpec", "ModelConfig", "MoESpec", "SSMSpec",
    "layout_groups", "decode_step", "forward", "init_caches", "init_model",
    "prefill", "train_loss", "train_loss_and_stats",
]
