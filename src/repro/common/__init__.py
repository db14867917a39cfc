"""Shared substrate: the KGE config and hardware constants."""

from repro.common.hw import TPU_V5E
from repro.common.config import KGEConfig

__all__ = [
    "TPU_V5E",
    "KGEConfig",
]
