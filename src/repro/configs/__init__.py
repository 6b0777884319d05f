"""Architecture registry: importing this package registers every assigned
architecture (``--arch <id>``) plus the reduced smoke variants."""
from repro.configs import (  # noqa: F401
    glm4_9b,
    granite_3_8b,
    qwen3_1_7b,
    mistral_nemo_12b,
    xlstm_125m,
    jamba_1_5_large,
    seamless_m4t_large_v2,
    grok_1_314b,
    granite_moe_3b_a800m,
    granite_4_0_h_small,
    phi_3_vision_4_2b,
    paper_lm,
)
