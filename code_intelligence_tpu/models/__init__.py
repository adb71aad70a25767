from code_intelligence_tpu.models.afmoe import AfmoeConfig, AfmoeEncoder
from code_intelligence_tpu.models.awd_lstm import (
    AWDLSTMConfig,
    AWDLSTMEncoder,
    AWDLSTMLM,
    init_lstm_states,
)
from code_intelligence_tpu.models.bailing_hybrid import (
    BailingHybridConfig,
    BailingHybridEncoder,
)
from code_intelligence_tpu.models.contract import (
    ChunkEncoder,
    build_encoder,
    make_config,
)
from code_intelligence_tpu.models.deepseek_v3 import (
    DeepseekV3Config,
    DeepseekV3Encoder,
)
from code_intelligence_tpu.models.evabyte import EvaByteConfig, EvaByteEncoder
from code_intelligence_tpu.models.glm_moe_dsa import (
    GlmMoeDsaConfig,
    GlmMoeDsaEncoder,
)
from code_intelligence_tpu.models.granite_hybrid import (
    GraniteHybridConfig,
    GraniteHybridEncoder,
)
from code_intelligence_tpu.models.longcat_flash import (
    LongcatFlashConfig,
    LongcatFlashEncoder,
)
from code_intelligence_tpu.models.qwen3_next import (
    Qwen3NextConfig,
    Qwen3NextEncoder,
)
from code_intelligence_tpu.models.smallthinker import (
    SmallThinkerConfig,
    SmallThinkerEncoder,
)

__all__ = ["AfmoeConfig", "AfmoeEncoder", "AWDLSTMConfig", "AWDLSTMEncoder", "AWDLSTMLM", "init_lstm_states",
           "BailingHybridConfig", "BailingHybridEncoder",
           "ChunkEncoder", "build_encoder", "make_config",
           "DeepseekV3Config", "DeepseekV3Encoder",
           "EvaByteConfig", "EvaByteEncoder",
           "GlmMoeDsaConfig", "GlmMoeDsaEncoder",
           "GraniteHybridConfig", "GraniteHybridEncoder",
           "LongcatFlashConfig", "LongcatFlashEncoder",
           "Qwen3NextConfig", "Qwen3NextEncoder",
           "SmallThinkerConfig", "SmallThinkerEncoder"]
