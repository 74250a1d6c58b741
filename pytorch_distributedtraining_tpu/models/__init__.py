"""Model zoo: SR models from the reference plus the BASELINE ladder.

Reference models (both from missing local modules, SURVEY §2.4):
  - ``Net`` — ESPCN-style sub-pixel conv SR net
    (`/root/reference/Fairscale-DDP.py:13,74`)
  - ``SwinIR`` — lightweight shifted-window-attention SR transformer
    (`/root/reference/Stoke-DDP.py:33,206-208`)

BASELINE ladder (BASELINE.json): ResNet-18/50, GPT-2 125M, ViT-B/16.

Beyond both: ``MoEMLP`` (a Switch layer over "ep", ``.moe``: imported from
its module) and ``Glm4MoeLite`` (GLM-4.7-Flash: latent attention, a
sigmoid-routed dropless expert layer that is told which experts it holds)
and ``SmallThinker`` (SmallThinker-21BA3B: window and position-free full
layers 3:1 over grouped-query heads, a softmax router that reads the layer's
raw input; the held-experts layer is one piece of code for both,
``.held_experts``) and ``Afmoe`` (Trinity-Mini: the same layer kinds with
a norm on every head of q and k, a sigmoid gate on the core's output and a
norm after each branch as well as before it; GLM's expert layer as it
stands, 8 of 128 a token).

All models are Flax linen modules in NHWC (images) / [B, T, D] (sequences) —
the layouts XLA:TPU tiles best — with bf16-friendly parameterization.
Imports are lazy so pulling one model doesn't build the whole zoo.
"""

from importlib import import_module as _import_module

_LAZY = {
    "Net": ".sr_espcn",
    "pixel_shuffle": ".sr_espcn",
    "SwinIR": ".swinir",
    "stack_swinir_layer_params": ".swinir",
    "unstack_swinir_layer_params": ".swinir",
    "stack_layer_params": ".scan_utils",
    "unstack_layer_params": ".scan_utils",
    "remat_block": ".scan_utils",
    "ResNet": ".resnet",
    "ResNet18": ".resnet",
    "ResNet34": ".resnet",
    "ResNet50": ".resnet",
    "ResNet101": ".resnet",
    "GPT2": ".gpt2",
    "GPT2Config": ".gpt2",
    "cross_entropy_loss": ".gpt2",
    "Glm4MoeLite": ".glm4_moe_lite",
    "Glm4MoeLiteConfig": ".glm4_moe_lite",
    "Afmoe": ".afmoe",
    "AfmoeConfig": ".afmoe",
    "SmallThinker": ".smallthinker",
    "SmallThinkerConfig": ".smallthinker",
    "ViT": ".vit",
    "ViTConfig": ".vit",
    "ViTB16": ".vit",
}

__all__ = sorted(_LAZY)


def __getattr__(name):
    if name in _LAZY:
        try:
            mod = _import_module(_LAZY[name], __name__)
        except ModuleNotFoundError as e:
            raise AttributeError(f"{__name__}.{name} is not available: {e}") from e
        obj = getattr(mod, name)
        globals()[name] = obj
        return obj
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(set(globals()) | set(_LAZY))
