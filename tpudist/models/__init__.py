"""Model zoo with a by-name registry (reference C3).

The reference resolves architectures by string from torchvision's namespace
(``models.__dict__[args.arch]()``, ``distributed.py:39-40,131-137``). Here the
registry is explicit: ``create_model('resnet18', num_classes=1000, ...)``.
``model_names()`` plays the role of the reference's ``model_names`` list used
for argparse choices (``distributed.py:39-40``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict

from flax import linen as nn

from tpudist.models import resnet as _resnet_mod
from tpudist.models.resnet import (resnet18, resnet34, resnet50,  # noqa: F401
                                   resnet101, resnet152, ResNet)
from tpudist.models.layers import BatchNorm                        # noqa: F401

_REGISTRY: Dict[str, Callable[..., nn.Module]] = {}


def register_model(name: str, ctor: Callable[..., nn.Module] | None = None):
    """Register a constructor under ``name`` (decorator or direct call)."""
    if ctor is None:
        def deco(fn):
            _REGISTRY[name] = fn
            return fn
        return deco
    _REGISTRY[name] = ctor
    return ctor


for _n in ("resnet18", "resnet34", "resnet50", "resnet101", "resnet152",
           "resnext50_32x4d", "resnext101_32x8d",
           "wide_resnet50_2", "wide_resnet101_2"):
    register_model(_n, getattr(_resnet_mod, _n))

from tpudist.models import vit as _vit_mod                         # noqa: E402

for _n in ("vit_b_16", "vit_b_32", "vit_l_16", "vit_l_32",
           "vit_h_14"):
    register_model(_n, getattr(_vit_mod, _n))

from tpudist.models import vit_moe as _vit_moe_mod                 # noqa: E402

for _n in ("vit_moe_b_16", "vit_moe_s_16"):
    register_model(_n, getattr(_vit_moe_mod, _n))

from tpudist.models import vit_pipe as _vit_pipe_mod               # noqa: E402

for _n in ("vit_pipe_b_16", "vit_pipe_s_16"):
    register_model(_n, getattr(_vit_pipe_mod, _n))

from tpudist.models import alexnet as _alexnet_mod                 # noqa: E402
from tpudist.models import squeezenet as _squeezenet_mod           # noqa: E402
from tpudist.models import vgg as _vgg_mod                         # noqa: E402

register_model("alexnet", _alexnet_mod.alexnet)
for _n in ("vgg11", "vgg13", "vgg16", "vgg19",
           "vgg11_bn", "vgg13_bn", "vgg16_bn", "vgg19_bn"):
    register_model(_n, getattr(_vgg_mod, _n))
for _n in ("squeezenet1_0", "squeezenet1_1"):
    register_model(_n, getattr(_squeezenet_mod, _n))

from tpudist.models import densenet as _densenet_mod               # noqa: E402
from tpudist.models import googlenet as _googlenet_mod             # noqa: E402
from tpudist.models import inception as _inception_mod             # noqa: E402
from tpudist.models import mnasnet as _mnasnet_mod                 # noqa: E402
from tpudist.models import mobilenet as _mobilenet_mod             # noqa: E402
from tpudist.models import shufflenet as _shufflenet_mod           # noqa: E402

for _n in ("densenet121", "densenet161", "densenet169", "densenet201"):
    register_model(_n, getattr(_densenet_mod, _n))
for _n in ("mobilenet_v2", "mobilenet_v3_large", "mobilenet_v3_small"):
    register_model(_n, getattr(_mobilenet_mod, _n))
for _n in ("shufflenet_v2_x0_5", "shufflenet_v2_x1_0",
           "shufflenet_v2_x1_5", "shufflenet_v2_x2_0"):
    register_model(_n, getattr(_shufflenet_mod, _n))
for _n in ("mnasnet0_5", "mnasnet0_75", "mnasnet1_0", "mnasnet1_3"):
    register_model(_n, getattr(_mnasnet_mod, _n))
register_model("googlenet", _googlenet_mod.googlenet)
register_model("inception_v3", _inception_mod.inception_v3)

from tpudist.models import convnext as _convnext_mod                # noqa: E402
from tpudist.models import efficientnet as _efficientnet_mod        # noqa: E402

for _n in ("efficientnet_b0", "efficientnet_b1", "efficientnet_b2",
           "efficientnet_b3", "efficientnet_b4", "efficientnet_b5",
           "efficientnet_b6", "efficientnet_b7",
           "efficientnet_v2_s", "efficientnet_v2_m", "efficientnet_v2_l"):
    register_model(_n, getattr(_efficientnet_mod, _n))
for _n in ("convnext_tiny", "convnext_small", "convnext_base",
           "convnext_large"):
    register_model(_n, getattr(_convnext_mod, _n))

from tpudist.models import regnet as _regnet_mod                    # noqa: E402

for _n in _regnet_mod._VARIANTS:
    register_model(_n, getattr(_regnet_mod, _n))

from tpudist.models import swin as _swin_mod                        # noqa: E402

for _n in _swin_mod._VARIANTS:
    register_model(_n, getattr(_swin_mod, _n))

from tpudist.models import maxvit as _maxvit_mod                    # noqa: E402

register_model("maxvit_t", _maxvit_mod.maxvit_t)

from tpudist.models import decoder as _decoder_mod                  # noqa: E402

register_model("mellum2_12b_a2_5b", _decoder_mod.mellum2_12b_a2_5b)
register_model("mellum2_tiny", _decoder_mod.mellum2_tiny)
register_model("sdar_30b_a3b", _decoder_mod.sdar_30b_a3b)
register_model("sdar_tiny", _decoder_mod.sdar_tiny)
register_model("nemotron3_nano_30b_a3b", _decoder_mod.nemotron3_nano_30b_a3b)
register_model("nemotron3_tiny", _decoder_mod.nemotron3_tiny)
register_model("ouro_2_6b", _decoder_mod.ouro_2_6b)
register_model("ouro_tiny", _decoder_mod.ouro_tiny)
register_model("joyai_llm_flash", _decoder_mod.joyai_llm_flash)
register_model("joyai_tiny", _decoder_mod.joyai_tiny)


def model_names() -> list[str]:
    return sorted(_REGISTRY)


def model_fields(model: nn.Module) -> frozenset[str]:
    """What a built model takes: the fields of its dataclass. A model says
    there, where it is defined, that it takes ``remat``, ``flash``, a mesh
    axis or a holder's share; nothing restates it by name."""
    return frozenset(f.name for f in dataclasses.fields(model))


def model_with(model: nn.Module, arch: str,
               asked: dict[str, dict[str, Any]]) -> nn.Module:
    """``model`` told what a run wants of it: ``asked`` maps each flag or
    mesh axis that asks to the fields it sets. Refuses, naming who asked
    and the field ``arch`` lacks, rather than building the plain model: a
    run that silently is not what its flags say would mislabel benchmarks
    and mis-state the HBM / FLOPs trade."""
    has = model_fields(model)
    for who, fields in asked.items():
        lacks = sorted(set(fields) - has)
        if lacks:
            raise ValueError(
                f"{who} sets the model's field{'s'[:len(lacks) != 1]} "
                f"{', '.join(lacks)}, which '{arch}' "
                f"({type(model).__name__}) does not have")
    fields = {k: v for wanted in asked.values() for k, v in wanted.items()}
    return model.clone(**fields) if fields else model


def create_model(arch: str, **kwargs: Any) -> nn.Module:
    """Build a model by name (reference ``models.__dict__[args.arch]()``,
    ``distributed.py:131-137``). Raises with the available names on a miss,
    like argparse ``choices`` did."""
    if arch not in _REGISTRY:
        raise ValueError(f"Unknown arch '{arch}'. Available: {', '.join(model_names())}")
    if kwargs.pop("remat", False):
        # not handed to a **kw-swallowing constructor: asked of the model
        return model_with(_REGISTRY[arch](**kwargs), arch,
                          {"--remat": dict(remat=True)})
    return _REGISTRY[arch](**kwargs)
