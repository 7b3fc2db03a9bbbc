"""Model zoo (reference: org.deeplearning4j.zoo.model.* — SURVEY.md §2.2).

No pretrained-weight downloads (zero-egress environment); architectures are
construction-parity with the reference and train from scratch.
"""

from ...generate.sampling import greedy, temperature, top_k, top_p
from .bert import BertEncoder
from .darknet import Darknet19, TinyYOLO
from .evabyte import EvaByteLM
from .inception_resnet import InceptionResNetV1
from .lenet import LeNet
from .lfm2_moe import Lfm2MoeLM
from .longcat_flash import LongCatFlashLM
from .misc import FaceNetNN4Small2, SimpleCNN, YOLO2
from .resnet50 import ResNet50
from .squeezenet import SqueezeNet
from .textgen_lstm import TextGenerationLSTM
from .transformer_lm import TransformerLM
from .unet import UNet
from .vgg16 import AlexNet, VGG16, VGG19
from .xception import Xception
from .nasnet import NASNet
from .phi4_flash import Phi4FlashLM
from .pangu_ultra_moe import PanguUltraMoeLM

__all__ = [
    "AlexNet",
    "BertEncoder",
    "Darknet19",
    "EvaByteLM",
    "FaceNetNN4Small2",
    "InceptionResNetV1",
    "LeNet",
    "Lfm2MoeLM",
    "LongCatFlashLM",
    "ResNet50",
    "SimpleCNN",
    "SqueezeNet",
    "TextGenerationLSTM",
    "TinyYOLO",
    "TransformerLM",
    "UNet",
    "greedy",
    "temperature",
    "top_k",
    "top_p",
    "VGG16",
    "VGG19",
    "YOLO2",
    "Xception",
    "NASNet",
    "Phi4FlashLM",
    "PanguUltraMoeLM",
]
