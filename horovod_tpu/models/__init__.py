"""The framework's model zoo: MNIST MLP, the ResNet family, VGG and
Inception-v3, the BERT family (SURVEY.md §6; BASELINE.json configs 1-3), a
GPT-style causal decoder, and SDAR-MoE (a Qwen3-MoE decoder of grouped-query
attention and top-k routed experts, trained by block diffusion), and ZAYA1
(compressed convolutional attention, a top-1 mixture whose MLP router carries
a state down the layers, a tied head; its loss is ``zaya.lm_loss``) and
``Jamba`` (Mamba-1 selective-scan layers with an attention layer every
``attn_layer_period``, a dense SwiGLU feed-forward a block, a tied head; each
layer a share of a tensor-parallel one where the configuration says so; its
loss is ``jamba.lm_loss``) and ``Laguna`` (sliding-window attention layers
among global ones with different head counts and rotary rules, a gate a head,
a top-k mixture of experts beside a shared one, an untied head; its loss is
``laguna.lm_loss``) and ``JoyAI`` (multi-head latent attention: a low-rank
latent between the stream and the heads, scores of two products, one rotary
key for all heads; experts chosen by bias-corrected sigmoid scores beside a
shared one; its loss is ``joyai.lm_loss``) and ``Sala`` (MiniCPM-SALA:
lightning linear-attention layers, one decay a head as chunked products on the
MXU, among block-selected softmax-attention layers whose visible keys the
data chooses, output gates on both, in MiniCPM's scaled frame; its loss is
``sala.lm_loss``) and ``Phi4Flash`` (Phi-4-mini-flash's SambaY: Mamba-1 and
banded differential-attention layers, then a cross-decoder whose gate layers
read one scan's output and whose attention layers read one layer's keys and
values, carried beside the residual stream; its loss is
``phi4flash.lm_loss``)."""

from .losses import softmax_cross_entropy  # noqa: F401
from .mlp import MLP, xent_loss  # noqa: F401
from .resnet import (  # noqa: F401
    ResNet, ResNet18, ResNet34, ResNet50, ResNet101, ResNet152, ResNetTiny,
)
from .bert import (  # noqa: F401
    BertConfig, BertEncoder, BertForPreTraining, mlm_loss, nsp_loss,
    pretraining_loss,
    BERT_BASE, BERT_LARGE, BERT_TINY,
)
from .gpt import (  # noqa: F401
    GPT, GPTConfig, GPT_SMALL, GPT_TINY, lm_loss,
)
from .sdar import (  # noqa: F401
    SDAR, SDARConfig, SDAR_30B_A3B, SDAR_TINY, block_diffusion_loss,
    noise_blocks,
)
from . import zaya  # noqa: F401
from .zaya import Zaya, ZayaConfig, ZAYA1_8B, ZAYA_TINY  # noqa: F401
from .vgg import VGG, VGG16, VGG19, VGGTiny  # noqa: F401
from .inception import InceptionV3  # noqa: F401
from . import jamba  # noqa: F401
from .jamba import Jamba, JambaConfig, JAMBA2_3B, JAMBA_TINY  # noqa: F401
from . import laguna  # noqa: F401
from .laguna import (  # noqa: F401
    Laguna, LagunaConfig, LAGUNA_S_2_1, LAGUNA_TINY,
)
from . import joyai  # noqa: F401
from .joyai import (  # noqa: F401
    JoyAI, JoyAIConfig, JOYAI_LLM_FLASH, JOYAI_TINY,
)
from . import sala  # noqa: F401
from .sala import Sala, SalaConfig, MINICPM_SALA, SALA_TINY  # noqa: F401
from . import phi4flash  # noqa: F401
from .phi4flash import (  # noqa: F401
    Phi4Flash, Phi4FlashConfig, PHI4_MINI_FLASH, PHI4FLASH_TINY,
)
