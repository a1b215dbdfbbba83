"""horovod_tpu — a TPU-native distributed training framework.

A ground-up rebuild of the capabilities of Horovod (reference:
``zhouxhao/horovod``, layout-identical to upstream ``horovod/horovod``):
the familiar ``hvd.init`` / ``hvd.allreduce`` / ``DistributedOptimizer``
API and the ``horovodrun`` launcher, re-founded on JAX/XLA for TPU.

Architecture (see SURVEY.md for the reference analysis):

- ``csrc/``            — the native C++ core runtime: background coordination
                         loop, coordinator-rank tensor negotiation, response
                         cache, tensor-fusion buffer, TCP control plane and a
                         ring-collective CPU data plane (the Gloo analog).
                         Reference: ``horovod/common/`` (operations.cc,
                         controller.cc, tensor_queue.cc, ...).
- ``horovod_tpu.jax``  — the new JAX frontend (reference has none; API parity
                         with ``horovod/torch/__init__.py`` + eager ops).
- ``horovod_tpu.torch``— PyTorch frontend (reference: ``horovod/torch/``).
- ``horovod_tpu.parallel`` — TPU-native in-graph SPMD path: device meshes,
                         sharding rules, ring-attention sequence parallelism.
                         Net-new vs the reference (SURVEY.md §5.7).
- ``horovod_tpu.runner`` — the ``horovodrun`` launcher (reference:
                         ``horovod/runner/``).
"""

import time as _time

from horovod_tpu.version import __version__  # noqa: F401


def run(*args, **kwargs):
    """In-python local launcher (reference analog: ``horovod.run``)."""
    from horovod_tpu.runner import run as _run

    return _run(*args, **kwargs)


# The start-up mark ``hvd.imported`` (utils/spans.py, which imports jax
# and so cannot be imported from here): the end of this import, on the
# monotonic clock.
_imported_at = _time.monotonic()
