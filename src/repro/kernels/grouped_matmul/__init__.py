"""Grouped matmul over a ragged set of experts (``moe_gmm``)."""

from .ops import Layout, gmm, gmm_tiles, layout
from .ref import gmm_ref

__all__ = ["Layout", "gmm", "gmm_ref", "gmm_tiles", "layout"]
