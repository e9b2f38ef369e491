from repro.utils.tree import (
    tree_bytes,
    tree_param_count,
    tree_flatten_with_paths,
    tree_map_with_path,
)
from repro.utils.hlo import collective_bytes, CollectiveStats

__all__ = [
    "tree_bytes",
    "tree_param_count",
    "tree_flatten_with_paths",
    "tree_map_with_path",
    "collective_bytes",
    "CollectiveStats",
]
