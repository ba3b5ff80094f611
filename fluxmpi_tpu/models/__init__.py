"""Model zoo mirroring the reference's benchmark configs (BASELINE.md):

1. :class:`MLP` — README quick-start 4-layer perceptron.
2. :class:`CNN` — Conv+BatchNorm CIFAR-10 net.
3. :class:`ResNet50` — the headline ImageNet DP workload.
4. :class:`DEQ` — deep equilibrium model with implicit-gradient custom VJP.
5. :class:`TransformerEncoder` — the wrapped-model adapter path.

Beyond the five parity configs: ResNet-18/34/101, :class:`TransformerLM`,
:class:`DecoderLM` (a decoder block read from a configuration: RMSNorm
sandwich or pre-norm, gated MLP, rotary, grouped K/V heads, window, full
and latent-attention layers, and
:class:`ExpertMLP`, routed experts without dropped tokens),
Switch-MoE variants, :class:`ViT` (patch-conv + the same encoder stack;
composes with the flash/ring/Ulysses ``attention_fn`` hooks), and
:class:`UNet` with the DDPM/DDIM helpers (generative vision — GroupNorm
conv stages + spatial self-attention on the same ``attention_fn`` hook).
"""

from .mlp import MLP  # noqa: F401
from .cnn import CNN  # noqa: F401
from .moe import (  # noqa: F401
    MoEEncoder,
    MoEEncoderBlock,
    MoEMLP,
    MoETransformerLM,
    collect_moe_losses,
    expert_parallel_rules,
)
from .resnet import (  # noqa: F401
    ResNet,
    ResNet18,
    ResNet34,
    ResNet50,
    ResNet101,
)
from .deq import DEQ, fixed_point_solve  # noqa: F401
from .transformer import TransformerEncoder, TransformerLM  # noqa: F401
from .decoder import DecoderConfig, DecoderLM, ExpertMLP, Keeps  # noqa: F401
from .generate import beam_search, generate  # noqa: F401
from .hf_gpt2 import lm_from_gpt2  # noqa: F401
from .vit import ViT  # noqa: F401
from .unet import (  # noqa: F401
    UNet,
    cosine_beta_schedule,
    ddim_sample,
    ddpm_loss,
)
