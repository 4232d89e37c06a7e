from dgc_tpu.models.resnet_cifar import CifarResNet, resnet20, resnet110
from dgc_tpu.models.resnet_imagenet import ResNet, resnet18, resnet50
from dgc_tpu.models.sambay import SambaY, phi4_mini_flash, published_layers
from dgc_tpu.models.vgg import VGG, vgg16_bn

__all__ = ["CifarResNet", "resnet20", "resnet110",
           "ResNet", "resnet18", "resnet50", "VGG", "vgg16_bn",
           "SambaY", "phi4_mini_flash", "published_layers"]
