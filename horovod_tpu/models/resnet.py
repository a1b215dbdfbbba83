"""ResNet v1.5 family, TPU-first.

Reference analog: the reference's headline benchmark models
(docs/benchmarks.rst: ResNet-50/101 in tf_cnn_benchmarks via
examples/). Functional jax instead of torch nn.Module:

- NHWC layout (TPU's native conv layout — the MXU consumes the channel
  minor dimension directly; torch's NCHW would force transposes).
- params and batchnorm running stats are separate pytrees; forward is
  pure: ``resnet_forward(params, state, x, train=...)`` returns
  ``(logits, new_state)`` — jit/grad/shard_map compose cleanly.
- bf16 compute / f32 params + batchnorm statistics.
- stride-on-3x3 (v1.5), matching the torchvision weights the reference
  benchmarks load.
"""

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.utils.spans import scope

# depths per stage for each family member
_DEPTHS = {
    18: ((2, 2, 2, 2), False),
    34: ((3, 4, 6, 3), False),
    50: ((3, 4, 6, 3), True),
    101: ((3, 4, 23, 3), True),
    152: ((3, 8, 36, 3), True),
}


@dataclasses.dataclass(frozen=True)
class ResNetConfig:
    depth: int = 50
    num_classes: int = 1000
    width: int = 64
    compute_dtype: str = "bfloat16"
    bn_momentum: float = 0.9
    bn_eps: float = 1e-5

    @property
    def stage_depths(self):
        return _DEPTHS[self.depth][0]

    @property
    def bottleneck(self):
        return _DEPTHS[self.depth][1]


def _conv_init(key, kh, kw, cin, cout):
    fan_in = kh * kw * cin
    return jax.random.normal(key, (kh, kw, cin, cout), jnp.float32) * (
        (2.0 / fan_in) ** 0.5)


def _bn_init(c):
    return {"scale": jnp.ones(c), "bias": jnp.zeros(c)}


def _bn_state(c):
    return {"mean": jnp.zeros(c), "var": jnp.ones(c)}


def resnet_init(config, key):
    """Returns (params, state): state holds batchnorm running stats."""
    c = config
    keys = iter(jax.random.split(key, 4 + sum(c.stage_depths) * 4))
    params = {"stem": {"conv": _conv_init(next(keys), 7, 7, 3, c.width),
                       "bn": _bn_init(c.width)}}
    state = {"stem": {"bn": _bn_state(c.width)}}
    cin = c.width
    expansion = 4 if c.bottleneck else 1
    for s, depth in enumerate(c.stage_depths):
        cmid = c.width * (2 ** s)
        cout = cmid * expansion
        blocks_p, blocks_s = [], []
        for b in range(depth):
            stride = 2 if (s > 0 and b == 0) else 1
            bp, bs = {}, {}
            if c.bottleneck:
                bp["conv1"] = _conv_init(next(keys), 1, 1, cin, cmid)
                bp["conv2"] = _conv_init(next(keys), 3, 3, cmid, cmid)
                bp["conv3"] = _conv_init(next(keys), 1, 1, cmid, cout)
                for i, ch in (("1", cmid), ("2", cmid), ("3", cout)):
                    bp[f"bn{i}"] = _bn_init(ch)
                    bs[f"bn{i}"] = _bn_state(ch)
                # zero-init the last BN scale (standard trick: the block
                # starts as identity, stabilizing early large-batch training)
                bp["bn3"]["scale"] = jnp.zeros(cout)
            else:
                bp["conv1"] = _conv_init(next(keys), 3, 3, cin, cmid)
                bp["conv2"] = _conv_init(next(keys), 3, 3, cmid, cout)
                for i, ch in (("1", cmid), ("2", cout)):
                    bp[f"bn{i}"] = _bn_init(ch)
                    bs[f"bn{i}"] = _bn_state(ch)
                bp["bn2"]["scale"] = jnp.zeros(cout)
            if cin != cout or stride != 1:
                bp["proj"] = _conv_init(next(keys), 1, 1, cin, cout)
                bp["proj_bn"] = _bn_init(cout)
                bs["proj_bn"] = _bn_state(cout)
            blocks_p.append(bp)
            blocks_s.append(bs)
            cin = cout
        params[f"stage{s}"] = blocks_p
        state[f"stage{s}"] = blocks_s
    params["head"] = {
        "w": jax.random.normal(next(keys), (cin, c.num_classes),
                               jnp.float32) * (cin ** -0.5),
        "b": jnp.zeros(c.num_classes)}
    return params, state


def _conv(x, w, stride=1, dtype=jnp.bfloat16):
    return lax.conv_general_dilated(
        x.astype(dtype), w.astype(dtype), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _batch_norm(x, p, s, train, momentum, eps):
    """Returns (y, new_running_stats). Stats in f32."""
    xf = x.astype(jnp.float32)
    if train:
        mean = xf.mean(axis=(0, 1, 2))
        var = xf.var(axis=(0, 1, 2))
        new_s = {"mean": momentum * s["mean"] + (1 - momentum) * mean,
                 "var": momentum * s["var"] + (1 - momentum) * var}
    else:
        mean, var = s["mean"], s["var"]
        new_s = s
    y = (xf - mean) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    return y.astype(x.dtype), new_s


def resnet_forward(params, state, x, config, train=True):
    """x [N,H,W,3] float -> (logits [N,classes] f32, new_state)."""
    c = config
    dt = jnp.dtype(c.compute_dtype)
    bn = partial(_batch_norm, train=train, momentum=c.bn_momentum,
                 eps=c.bn_eps)
    new_state = {"stem": {}}
    with scope("hvd.cnn.stem"):
        h = _conv(x.astype(dt), params["stem"]["conv"], stride=2, dtype=dt)
        h, new_state["stem"]["bn"] = bn(h, params["stem"]["bn"],
                                        state["stem"]["bn"])
        h = jax.nn.relu(h)
        h = lax.reduce_window(h, -jnp.inf, lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")
    for s in range(len(c.stage_depths)):
        stage_state = []
        for b, bp in enumerate(params[f"stage{s}"]):
            h, nbs = _block(h, bp, state[f"stage{s}"][b], c, bn, dt,
                            stride=2 if (s > 0 and b == 0) else 1,
                            stage=s + 1)
            stage_state.append(nbs)
        new_state[f"stage{s}"] = stage_state
    with scope("hvd.cnn.head"):
        pooled = h.astype(jnp.float32).mean(axis=(1, 2))
        logits = pooled @ params["head"]["w"] + params["head"]["b"]
    return logits, new_state


def _block(h, bp, bs, c, bn, dt, stride, stage):
    """One residual block of stage ``stage`` (1-based; a fifth stage and
    beyond would read as the fourth) -> (h, the block's new batch-norm
    statistics)."""
    nbs = {}
    with scope(f"hvd.cnn.stage{stage}"):
        shortcut = h
        if "proj" in bp:
            shortcut = _conv(h, bp["proj"], stride=stride, dtype=dt)
            shortcut, nbs["proj_bn"] = bn(shortcut, bp["proj_bn"],
                                          bs["proj_bn"])
        if c.bottleneck:
            y = _conv(h, bp["conv1"], dtype=dt)
            y, nbs["bn1"] = bn(y, bp["bn1"], bs["bn1"])
            y = jax.nn.relu(y)
            y = _conv(y, bp["conv2"], stride=stride, dtype=dt)  # v1.5
            y, nbs["bn2"] = bn(y, bp["bn2"], bs["bn2"])
            y = jax.nn.relu(y)
            y = _conv(y, bp["conv3"], dtype=dt)
            y, nbs["bn3"] = bn(y, bp["bn3"], bs["bn3"])
        else:
            y = _conv(h, bp["conv1"], stride=stride, dtype=dt)
            y, nbs["bn1"] = bn(y, bp["bn1"], bs["bn1"])
            y = jax.nn.relu(y)
            y = _conv(y, bp["conv2"], dtype=dt)
            y, nbs["bn2"] = bn(y, bp["bn2"], bs["bn2"])
        return jax.nn.relu(y + shortcut), nbs


def resnet_loss(params, state, batch, config, train=True):
    """Softmax CE; batch = {"images": [N,H,W,3], "labels": [N]}."""
    logits, new_state = resnet_forward(params, state, batch["images"],
                                       config, train=train)
    with scope("hvd.cnn.head"):
        logp = jax.nn.log_softmax(logits)
        nll = -jnp.take_along_axis(logp, batch["labels"][:, None], axis=-1)
        return nll.mean(), new_state


def resnet_partition_rules():
    """Data-parallel by default: conv weights replicated, batch over
    data axes. (The reference's benchmark setup — pure DP.)"""
    from jax.sharding import PartitionSpec as P

    return [(r".*", P())]
