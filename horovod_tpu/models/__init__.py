"""horovod_tpu.models — JAX-native model zoo for examples and benchmarks.

The reference ships models only as examples (examples/pytorch_mnist.py,
keras resnet, BERT scripts — SURVEY.md §1 top layer); here they are proper
library code because the flagship transformer doubles as the perf vehicle
for the sharding/ring-attention machinery in ``horovod_tpu.parallel``.
"""

from horovod_tpu.models.llama import (  # noqa: F401
    LlamaConfig,
    llama_exit_terms,
    llama_expert_load,
    llama_forward,
    llama_init,
    llama_loss,
    llama_partition_rules,
)
from horovod_tpu.models.generate import (  # noqa: F401
    llama_decode_step,
    llama_generate,
    llama_prefill,
)
from horovod_tpu.models.mlp import mlp_forward, mlp_init  # noqa: F401
from horovod_tpu.models.resnet import (  # noqa: F401
    ResNetConfig,
    resnet_forward,
    resnet_init,
    resnet_loss,
    resnet_partition_rules,
)
from horovod_tpu.models.bert import (  # noqa: F401
    BertConfig,
    bert_forward,
    bert_init,
    bert_mlm_loss,
    bert_partition_rules,
)
