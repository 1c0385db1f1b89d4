from deepspeed_tpu.models.config import TransformerConfig, bert_config, gpt2_config, llama_config, qwen2_config
from deepspeed_tpu.models.moe_transformer import (
    MoETransformerConfig,
    MoETransformerLM,
    mixtral_config,
    moe_llama_config,
    olmoe_config,
)
from deepspeed_tpu.models.transformer import TransformerLM, cross_entropy_loss
from deepspeed_tpu.models.unet import (
    AutoencoderKL,
    UNet2DConditionModel,
    UNetConfig,
    VAEConfig,
)
