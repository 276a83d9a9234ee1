"""deepseek-v3-ep32-5l [moe] — one chip's share of DeepSeek-V3 served
expert-parallel over 32 chips, cut to one pipeline stage of 5 layers.

The deployment is DeepSeek's prefill unit (github.com/deepseek-ai/
open-infra-index): routed experts over 32 chips (EP32), MLA and the shared
expert data-parallel (DP32), no redundant experts.  This chip holds
experts 0-7 of every expert layer (group 0 of 8), the whole router, MLA,
shared expert, embedding and head.  Depth: 1 dense layer (the published
3 leading dense layers count once) and 4 expert layers of the published
61; the other layers would be further pipeline stages.

Published router (config.json): ``scoring_func`` sigmoid, ``topk_method``
noaux_tc with ``e_score_correction_bias``, 8 groups of which 4 are kept,
top-8, ``norm_topk_prob``, ``routed_scaling_factor`` 2.5.  Published
``rope_scaling``: YaRN, factor 40 over 4096 original positions,
``beta_fast`` 32, ``beta_slow`` 1, ``mscale`` = ``mscale_all_dim`` = 1.
The MTP module is out of scope.
"""

import dataclasses

from repro.configs.base import YarnConfig
from repro.configs.deepseek_v3_671b import CONFIG as _V3

CONFIG = dataclasses.replace(
    _V3,
    name="deepseek-v3-ep32-5l",
    num_layers=5,
    yarn=YarnConfig(factor=40.0, original_max_position_embeddings=4096,
                    beta_fast=32.0, beta_slow=1.0, mscale=1.0,
                    mscale_all_dim=1.0),
    moe=dataclasses.replace(
        _V3.moe,
        n_dense_layers=1,
        n_group=8,
        topk_group=4,
        routed_scaling_factor=2.5,
        experts_held=8,
        expert_offset=0,
    ),
    source="arXiv:2412.19437; hf config.json; deepseek-ai/open-infra-index",
)
