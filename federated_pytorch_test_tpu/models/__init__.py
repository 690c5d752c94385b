from federated_pytorch_test_tpu.models.base import BlockModule, to_plain_dict  # noqa: F401
from federated_pytorch_test_tpu.models.simple import Net, Net1, Net2  # noqa: F401
from federated_pytorch_test_tpu.models.resnet import (  # noqa: F401
    BasicBlock,
    Bottleneck,
    ResNet,
    ResNet9,
    ResNet18,
)
from federated_pytorch_test_tpu.models.vae import AutoEncoderCNN  # noqa: F401
from federated_pytorch_test_tpu.models.vae_cl import AutoEncoderCNNCL  # noqa: F401
from federated_pytorch_test_tpu.models.cpc import (  # noqa: F401
    ContextgenCNN,
    EncoderCNN,
    PredictorCNN,
)
from federated_pytorch_test_tpu.models.qwen3_next import Qwen3Next  # noqa: F401
from federated_pytorch_test_tpu.models.glm4_moe_lite import Glm4MoeLite  # noqa: F401
from federated_pytorch_test_tpu.models.xing4_0 import Xing4  # noqa: F401
from federated_pytorch_test_tpu.models.zaya import Zaya  # noqa: F401
from federated_pytorch_test_tpu.models.olmo_hybrid import OlmoHybrid  # noqa: F401

MODEL_REGISTRY = {
    "net": Net,
    "net1": Net1,
    "net2": Net2,
    "resnet9": ResNet9,
    "resnet18": ResNet18,
    "vae": AutoEncoderCNN,
    "vae_cl": AutoEncoderCNNCL,
    "cpc_encoder": EncoderCNN,
    "cpc_contextgen": ContextgenCNN,
    "cpc_predictor": PredictorCNN,
    "qwen3_next": Qwen3Next,
    "glm4_moe_lite": Glm4MoeLite,
    "xing4_0": Xing4,
    "zaya": Zaya,
    "olmo_hybrid": OlmoHybrid,
}


def get_model(name: str, **kwargs):
    return MODEL_REGISTRY[name](**kwargs)
