from .rules import (cache_spec, constrain, dp_axes, param_sharding_tree,
                    param_spec, tp_axis, tree_paths)

__all__ = ["cache_spec", "constrain", "dp_axes", "param_sharding_tree",
           "param_spec", "tp_axis", "tree_paths"]
