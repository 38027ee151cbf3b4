"""Architecture registry.  Importing this package registers every assigned
architecture; ``get_config(name)`` / ``smoke_config(name)`` fetch them."""

from repro.configs.base import (  # noqa: F401
    ModelConfig,
    ShapeSpec,
    SHAPES,
    get_config,
    list_archs,
    shape_cells,
    smoke_config,
)

# one import per assigned architecture — registration is a side effect
from repro.configs import (  # noqa: F401
    deepseek_v2_lite,
    granite_3_2b,
    granite_moe_1b,
    mamba2_2_7b,
    qwen1_5_110b,
    qwen2_5_14b,
    qwen2_moe_a2_7b,
    qwen2_vl_72b,
    qwen3_14b,
    seamless_m4t_large_v2,
    zamba2_1_2b,
)
from repro.configs import nn_benchmarks  # noqa: F401
