"""The paper's join problem sizes (§5): one ``JoinConfig`` per workload."""
from repro.configs.paper_knn import SYNTHETIC, YEAST_WORM, JoinConfig

__all__ = ["JoinConfig", "SYNTHETIC", "YEAST_WORM"]
