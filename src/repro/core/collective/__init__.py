"""TPU-native adaptation of Canary: multi-root tree collectives over mesh
axes with congestion-oracle block scheduling (DESIGN.md §4)."""
from .api import canary_allreduce_tree
from .congestion import CongestionOracle, round_robin_roots, tree_link_load
from .trees import (hierarchical_allreduce, multi_root_tree_allreduce,
                    ring_allreduce, tree_reduce_broadcast)

__all__ = ["CongestionOracle", "canary_allreduce_tree",
           "hierarchical_allreduce", "multi_root_tree_allreduce",
           "ring_allreduce", "round_robin_roots", "tree_link_load",
           "tree_reduce_broadcast"]
