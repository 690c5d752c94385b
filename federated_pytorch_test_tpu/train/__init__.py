"""Training engine: blockwise-federated loop nest + algorithm strategies.

The reference duplicates one ~120-line driver skeleton across 6 scripts
(SURVEY.md "Shared driver skeleton"); here it is one engine
(:class:`~federated_pytorch_test_tpu.train.engine.BlockwiseFederatedTrainer`)
parameterised by an algorithm strategy (fedavg / fedprox / admm / none).
"""

from federated_pytorch_test_tpu.train.config import FederatedConfig  # noqa: F401
from federated_pytorch_test_tpu.train.algorithms import (  # noqa: F401
    FedAvg,
    FedProx,
    AdmmConsensus,
    NoConsensus,
)
from federated_pytorch_test_tpu.train.engine import BlockwiseFederatedTrainer  # noqa: F401
from federated_pytorch_test_tpu.train.cpc_engine import CPCTrainer  # noqa: F401
from federated_pytorch_test_tpu.train.vae_engine import (  # noqa: F401
    VAECLTrainer,
    VAETrainer,
)
from federated_pytorch_test_tpu.train.lm_engine import LMTrainer  # noqa: F401
