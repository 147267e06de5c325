"""Assigned architecture registry: importing this package registers all archs.

Each module defines the EXACT published config plus a reduced smoke config of
the same family (small depth/width, few experts, tiny vocab) used by the CPU
smoke tests.  Full configs are only ever lowered abstractly (dry-run).
"""
from repro.configs.base import (  # noqa: F401
    ArchConfig,
    MoEConfig,
    PlanConfig,
    ShapeSpec,
    SHAPES,
    get_config,
    list_archs,
)

# registration side effects
from repro.configs import (  # noqa: F401
    hubert_xlarge,
    internvl2_76b,
    qwen2_7b,
    granite_20b,
    llama3_405b,
    stablelm_12b,
    recurrentgemma_9b,
    moonshot_v1_16b_a3b,
    granite_moe_1b_a400m,
    mamba2_1p3b,
    granite_4_0_h_micro,
    tiny,
)
