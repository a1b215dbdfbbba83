"""Single-chip ResNet-50 training throughput (images/sec).

The reference's headline benchmark family is tf_cnn_benchmarks
ResNet/Inception images-per-second at scale (BASELINE.md: ~90% of
linear at 128 GPUs; BASELINE.json target: ResNet-50 images/sec/chip
with >=90% scaling efficiency). Multi-chip scaling needs a pod; this
bench records the per-chip leg on real hardware — synthetic ImageNet
(224x224), bf16 compute, SGD+momentum, one fused jit train step, the
same shape the reference benches.

Run on a real TPU chip::

    python benchmarks/resnet_bench.py [--out results.json]
"""

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--steps", type=int, default=10)
    args = ap.parse_args()

    import jax.numpy as jnp
    import numpy as np
    import optax

    from horovod_tpu.models import (
        ResNetConfig,
        resnet_init,
        resnet_loss,
    )

    from horovod_tpu.utils.compile_cache import enable_compile_cache
    from horovod_tpu.utils.devices import require_tpu

    require_tpu("resnet_bench")
    enable_compile_cache()

    cfg = ResNetConfig(depth=50)
    params, state = resnet_init(cfg, jax.random.PRNGKey(0))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    tx = optax.sgd(0.1, momentum=0.9)
    carry = (params, state, tx.init(params))
    del params, state

    images = jax.random.normal(jax.random.PRNGKey(1),
                               (args.batch, 224, 224, 3), jnp.bfloat16)
    labels = jax.random.randint(jax.random.PRNGKey(2), (args.batch,),
                                0, cfg.num_classes)
    batch = {"images": images, "labels": labels}

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(carry, batch):
        params, state, opt = carry
        (loss, state), grads = jax.value_and_grad(
            resnet_loss, has_aux=True)(params, state, batch, cfg)
        updates, opt = tx.update(grads, opt, params)
        return loss, (optax.apply_updates(params, updates), state, opt)

    t0 = time.time()
    loss, carry = step(carry, batch)
    # Materialize to host: block_until_ready returns early on some
    # PJRT transports (see decode_bench).
    np.asarray(loss)
    first_s = time.time() - t0
    t0 = time.time()
    for _ in range(args.steps):
        loss, carry = step(carry, batch)
    np.asarray(loss)
    dt = (time.time() - t0) / args.steps
    img_s = args.batch / dt
    # The reference's public per-GPU figure for context: ~195 img/s on
    # a Pascal P100 (tf_cnn_benchmarks era); modern accelerators are
    # far past it — vs_baseline normalizes against 1000 img/s/chip as
    # a round contemporary bar.
    row = {
        "metric": "resnet50_img_s",
        "value": round(img_s, 1),
        "unit": f"images/s ({n_params / 1e6:.0f}M params, ResNet-50 "
                f"bf16 train, batch {args.batch}, 224x224 synthetic, "
                f"{dt * 1e3:.0f} ms/step, first call incl compile "
                f"{first_s:.0f}s, {jax.devices()[0].device_kind})",
        "vs_baseline": round(img_s / 1000.0, 3),
    }
    print(json.dumps(row), flush=True)
    if args.out:
        payload = {
            "note": "ResNet-50 bf16 training on one real chip, "
                    "synthetic 224x224 ImageNet (the reference's "
                    "tf_cnn_benchmarks shape). vs_baseline normalizes "
                    "by a 1000 img/s/chip contemporary bar.",
            "rows": [row],
        }
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=1)


if __name__ == "__main__":
    main()
