"""Architecture registry: importing this package registers all configs."""
from repro.configs import (deepseek_v2_lite, deepseek_v3_2,  # noqa
                           granite_34b, jamba_1_5_large, minitron_4b,
                           mixtral_8x22b, olmo_1b, pixtral_12b,
                           qwen2_moe_a2_7b, qwen3_0_6b, rwkv6_1_6b,
                           whisper_base)
from repro.configs.base import ModelConfig, get_config, list_archs  # noqa
from repro.configs.shapes import (SHAPES, input_specs,  # noqa
                                  reduce_for_smoke, shape_supported)

# the model zoo (repro.models) builds these; deepseek-v2-lite and
# deepseek-v3.2 are lowered only into the PPA oracle's workload IR
# (repro.core.workloads.from_config)
ALL_ARCHS = (
    "olmo-1b", "granite-34b", "qwen3-0.6b", "minitron-4b", "mixtral-8x22b",
    "qwen2-moe-a2.7b", "jamba-1.5-large", "whisper-base", "rwkv6-1.6b",
    "pixtral-12b",
)
