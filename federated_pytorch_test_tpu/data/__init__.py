"""Host-side data pipelines feeding the client mesh.

TPU-native re-design of the reference's L3 data layer (SURVEY.md section 1):
the per-client ``DataLoader`` dicts (reference: federated_multi.py:52-85)
become dense ``[K, steps, batch, ...]`` numpy arrays built once on the host and
``jax.device_put`` along the ``clients`` mesh axis — no Python iterator in the
hot loop, no host round-trips between minibatches.
"""

from federated_pytorch_test_tpu.data.cifar10 import (  # noqa: F401
    FederatedCifar10,
    load_cifar10_arrays,
)
from federated_pytorch_test_tpu.data.lofar import (  # noqa: F401
    CPCDataSource,
    RoundPrefetcher,
    get_data_minibatch,
)
from federated_pytorch_test_tpu.data.tokens import FederatedTokens  # noqa: F401
