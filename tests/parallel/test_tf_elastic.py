"""TF/Keras elastic state, object collectives, and optimizer dispatch.

Reference analog: test/parallel/test_tensorflow.py (broadcast_object,
allgather_object) and the elastic state unit tests (SURVEY.md §4) —
distributed correctness via analytic closed forms on 2 local ranks.
"""

import numpy as np
import pytest

from tests import utils_mp

_TF_ENV = {"TF_CPP_MIN_LOG_LEVEL": "3", "CUDA_VISIBLE_DEVICES": ""}


@pytest.fixture(scope="module")
def tf_world():
    """As test_tensorflow_frontend.py's: the ranks start once a module."""
    with utils_mp.worlds("horovod_tpu.tensorflow", env=_TF_ENV) as world:
        yield world


def _worker_objects(rank, size):
    import horovod_tpu.tensorflow as hvd

    obj = hvd.broadcast_object({"lr": 0.1 * (rank + 1), "rank": rank},
                               root_rank=1)
    assert obj == {"lr": 0.2, "rank": 1}

    fn = hvd.broadcast_object_fn(root_rank=0)
    assert fn(["a", rank]) == ["a", 0]

    gathered = hvd.allgather_object({"rank": rank, "pad": "x" * rank})
    assert [g["rank"] for g in gathered] == list(range(size))
    return "ok"


@pytest.mark.parametrize("size", [2])
def test_tf_object_collectives(tf_world, size):
    assert tf_world(size).run(_worker_objects, timeout=180) == ["ok"] * size


def _worker_tf_state(rank, size):
    import tensorflow as tf
    import horovod_tpu.tensorflow as hvd

    v = tf.Variable(tf.fill([3], float(rank)))
    state = hvd.elastic.TensorFlowState(variables=[v], step=rank)

    # sync(): every rank adopts rank 0's snapshot.
    state.sync()
    np.testing.assert_allclose(v.numpy(), 0.0)
    assert state.step == 0

    # commit/restore round-trip.
    v.assign(tf.fill([3], 7.0))
    state.step = 11
    state.commit()
    v.assign(tf.fill([3], -1.0))
    state.step = 99
    state.restore()
    np.testing.assert_allclose(v.numpy(), 7.0)
    assert state.step == 11
    return "ok"


@pytest.mark.parametrize("size", [2])
def test_tf_elastic_state(tf_world, size):
    assert tf_world(size).run(_worker_tf_state, timeout=180) == ["ok"] * size


def _worker_keras_state(rank, size):
    import tensorflow as tf
    import horovod_tpu.tensorflow.keras as hvd

    tf.keras.utils.set_random_seed(1000 + rank)  # diverge per rank
    model = tf.keras.Sequential(
        [tf.keras.layers.Dense(4, input_shape=(3,)),
         tf.keras.layers.Dense(1)])
    opt = hvd.DistributedOptimizer(tf.keras.optimizers.SGD(0.1))
    model.compile(optimizer=opt, loss="mse")

    state = hvd.elastic.KerasState(model, batch=0, epoch=0)
    state.sync()
    # After sync all ranks hold identical (rank 0's) weights.
    digest = float(sum(np.sum(w) for w in model.get_weights()))
    all_digests = hvd.allgather_object(digest)
    assert all(abs(d - all_digests[0]) < 1e-6 for d in all_digests)

    x = np.random.RandomState(0).randn(8, 3).astype("float32")
    y = np.random.RandomState(1).randn(8, 1).astype("float32")
    cbs = [hvd.elastic.CommitStateCallback(state, batches_per_commit=2),
           hvd.elastic.UpdateBatchStateCallback(state),
           hvd.elastic.UpdateEpochStateCallback(state)]
    model.fit(x, y, batch_size=4, epochs=2, verbose=0, callbacks=cbs,
              initial_epoch=state.epoch)
    assert state.epoch == 2
    assert state.batch == 0  # reset at epoch end
    return "ok"


@pytest.mark.parametrize("size", [2])
def test_keras_elastic_state_and_callbacks(tf_world, size):
    assert tf_world(size).run(_worker_keras_state, timeout=240) \
        == ["ok"] * size


def _worker_tf_distopt_dispatch(rank, size):
    import tensorflow as tf
    import horovod_tpu.tensorflow as hvd

    # keras optimizer path: returns a genuine keras optimizer subclass.
    opt = hvd.DistributedOptimizer(tf.keras.optimizers.Adam(1e-3))
    assert isinstance(opt, tf.keras.optimizers.Adam)

    # Apply rank-dependent grads; vars must end identical (averaged).
    v = tf.Variable(tf.zeros([4]))
    opt.apply_gradients([(tf.fill([4], float(rank + 1)), v)])
    gathered = hvd.allgather_object(v.numpy().tolist())
    assert gathered[0] == gathered[-1]
    return "ok"


@pytest.mark.parametrize("size", [2])
def test_tf_distributed_optimizer_dispatch(tf_world, size):
    assert tf_world(size).run(_worker_tf_distopt_dispatch, timeout=180) \
        == ["ok"] * size


def _worker_v1_optimizer(rank, size):
    import tensorflow as tf
    import horovod_tpu.tensorflow as hvd

    opt = hvd.DistributedOptimizer(
        tf.compat.v1.train.GradientDescentOptimizer(0.5))
    assert isinstance(opt, tf.compat.v1.train.Optimizer)

    # loss grad = rank+1 on each rank → averaged grad is identical,
    # so after one minimize() the variable matches on every rank.
    v = tf.Variable([2.0])
    opt.minimize(lambda: v * float(rank + 1), var_list=[v])
    expected = 2.0 - 0.5 * (sum(range(1, size + 1)) / size)
    np.testing.assert_allclose(v.numpy(), [expected], rtol=1e-6)

    try:
        hvd.DistributedOptimizer(
            tf.compat.v1.train.GradientDescentOptimizer(0.5),
            backward_passes_per_step=4)
        raise AssertionError("expected ValueError")
    except ValueError:
        pass
    return "ok"


@pytest.mark.parametrize("size", [2])
def test_tf_v1_distributed_optimizer(tf_world, size):
    assert tf_world(size).run(_worker_v1_optimizer, timeout=180) \
        == ["ok"] * size
