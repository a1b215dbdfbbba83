"""Model adapter for kind "cnn": ResNet through the program's own
``ResNetConfig`` / ``resnet_init`` / ``resnet_loss``, on a fixed
device-resident batch of ImageNet shape (as upstream Horovod's synthetic
benchmarks use). Only the plain float32 reference is written here.
"""

import jax
import jax.numpy as jnp
from jax import lax

from chipbench import peaks
from chipbench.compare import rel_err

# bf16 convolutions (8 mantissa bits) through ~50 layers, each followed
# by a batch norm that renormalizes, against float32 at "highest": the
# logits agree to about a percent. A convolution computed below bf16
# precision, or a wrong stride or padding, moves them by tens of percent.
LOGITS_TOL = 5e-2
REFERENCE_IMAGES = 32


def reference_logits(params, images, eps):
    """The plain reference: bottleneck ResNet (He et al. 2015, with the
    stride in the 3x3 convolution, "v1.5") in float32 at full matmul
    precision, batch norm on batch statistics (training mode). Reads the
    program's parameter tree; shares no code with ``models/resnet.py``."""
    def conv(x, w, stride=1):
        return lax.conv_general_dilated(
            x, w.astype(jnp.float32), (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=lax.Precision.HIGHEST)

    def bn(x, p):
        mean = x.mean((0, 1, 2))
        var = ((x - mean) ** 2).mean((0, 1, 2))
        return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]

    h = jax.nn.relu(bn(conv(images.astype(jnp.float32),
                            params["stem"]["conv"], 2),
                       params["stem"]["bn"]))
    h = lax.reduce_window(h, -jnp.inf, lax.max, (1, 3, 3, 1),
                          (1, 2, 2, 1), "SAME")
    stage = 0
    while f"stage{stage}" in params:
        for b, bp in enumerate(params[f"stage{stage}"]):
            stride = 2 if (stage > 0 and b == 0) else 1
            short = h
            if "proj" in bp:
                short = bn(conv(h, bp["proj"], stride), bp["proj_bn"])
            y = jax.nn.relu(bn(conv(h, bp["conv1"]), bp["bn1"]))
            y = jax.nn.relu(bn(conv(y, bp["conv2"], stride), bp["bn2"]))
            y = bn(conv(y, bp["conv3"]), bp["bn3"])
            h = jax.nn.relu(y + short)
        stage += 1
    pooled = h.mean((1, 2))
    return jnp.matmul(pooled, params["head"]["w"],
                      precision=lax.Precision.HIGHEST) + params["head"]["b"]


class Model:
    unit = "images"

    def __init__(self, config, traffic):
        from horovod_tpu.models import ResNetConfig

        self.cfg = ResNetConfig(
            depth=config["depth"], num_classes=config["num_classes"],
            width=config["width"], compute_dtype=config["compute_dtype"],
            bn_momentum=config["bn_momentum"], bn_eps=config["bn_eps"])
        self.image = (config["image_size"], config["image_size"],
                      config["channels"])
        self.batch_size = traffic["batch"]
        self.units_per_step = self.batch_size
        self.opt = config["assumed"]["optimizer"]
        self.compiler_options = dict(
            config["assumed"].get("compiler_options") or {})
        self.has_state = True

    def init(self, key):
        from horovod_tpu.models import resnet_init

        return resnet_init(self.cfg, key)

    def loss(self, params, state, batch):
        from horovod_tpu.models import resnet_loss

        return resnet_loss(params, state, batch, self.cfg)

    def batch(self, key):
        ki, kl = jax.random.split(key)
        return {"images": jax.random.uniform(
                    ki, (self.batch_size,) + self.image, jnp.float32),
                "labels": jax.random.randint(
                    kl, (self.batch_size,), 0, self.cfg.num_classes)}

    def optimizer(self, ranks):
        import optax

        assert self.opt["name"] == "sgd", self.opt
        lr = self.opt["learning_rate"] * (
            ranks if self.opt.get("scale_by_ranks") else 1)
        return optax.sgd(lr, momentum=self.opt["momentum"])

    def flops_per_unit(self):
        assert self.cfg.depth == 50 and self.image[0] == 224
        return peaks.RESNET50_TRAIN_FLOPS_PER_IMAGE_224

    def check_lowering(self, text, on_tpu):
        return None

    def check_outputs(self, params, key, say):
        """Outside the window, once a run: the program's logits on a
        seeded sample (training-mode batch norm, the trained weights)
        against the plain float32 reference."""
        from horovod_tpu.models import resnet_forward

        images = jax.random.uniform(key, (REFERENCE_IMAGES,) + self.image,
                                    jnp.float32)
        _, state = jax.eval_shape(self.init, jax.random.PRNGKey(0))
        state = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), state)
        got = jax.jit(lambda p, s, x: resnet_forward(
            p, s, x, self.cfg, train=True)[0])(params, state, images)
        ref = jax.jit(lambda p, x: reference_logits(
            p, x, self.cfg.bn_eps))(params, images)
        e = rel_err(got, ref)
        say(event="logits_vs_reference", images=REFERENCE_IMAGES, err=e,
            tol=LOGITS_TOL)
        return [] if e <= LOGITS_TOL else [
            f"logits error {e} vs the float32 reference"]
