"""Architecture configs. Importing this package registers every config."""
from repro.configs.base import (  # noqa: F401
    INPUT_SHAPES,
    DeepSeekV3Config,
    InputShape,
    ModelConfig,
    get_config,
    get_shape,
    list_configs,
    smoke_variant,
)

# Registration side effects — one module per assigned architecture (+ paper's own).
from repro.configs import qwen3_8b  # noqa: F401
from repro.configs import qwen2_5_3b  # noqa: F401
from repro.configs import olmoe_1b_7b  # noqa: F401
from repro.configs import mamba2_780m  # noqa: F401
from repro.configs import kimi_k2_1t_a32b  # noqa: F401
from repro.configs import hubert_xlarge  # noqa: F401
from repro.configs import zamba2_1_2b  # noqa: F401
from repro.configs import internvl2_2b  # noqa: F401
from repro.configs import phi3_medium_14b  # noqa: F401
from repro.configs import granite_3_2b  # noqa: F401
from repro.configs import deepseek_r1  # noqa: F401
from repro.configs import deepseek_v3  # noqa: F401

ASSIGNED_ARCHS = [
    "qwen3-8b",
    "qwen2.5-3b",
    "olmoe-1b-7b",
    "mamba2-780m",
    "kimi-k2-1t-a32b",
    "hubert-xlarge",
    "zamba2-1.2b",
    "internvl2-2b",
    "phi3-medium-14b",
    "granite-3-2b",
]
