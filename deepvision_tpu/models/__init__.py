from deepvision_tpu.models.registry import get_model, list_models, register

# Imports for registration side effects.
from deepvision_tpu.models import (  # noqa: F401
    alexnet,
    centernet,
    gan,
    hourglass,
    hyper_latent,
    inception,
    latent_moe,
    lenet,
    mobilenet,
    resnet,
    shufflenet,
    transformer,
    vgg,
    yolo,
)

__all__ = ["get_model", "list_models", "register"]
