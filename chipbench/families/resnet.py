"""ResNet image classification (``horovod_tpu.models.resnet``).

``build(config, traffic)`` gives the harness the model, loss, optimizer and
seeded batch pool of one cell, and the work a step requires.
"""

import types


def build(config, traffic):
    import jax.numpy as jnp
    import numpy as np
    import optax
    import horovod_tpu as hvd
    from horovod_tpu.models.resnet import BottleneckResNetBlock, ResNet

    m, a = config["model"], config["assumed"]
    batch, size = traffic["global_batch"], m["image_size"]
    model = ResNet(stage_sizes=m["stage_sizes"],
                   block_cls=BottleneckResNetBlock,
                   num_classes=m["num_classes"], width=m["width"],
                   dtype=jnp.dtype(a["compute_dtype"]), stem=a["stem"],
                   axis_name=hvd.RANK_AXIS)        # sync-BatchNorm

    def loss_fn(logits, labels):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()

    def pool(seed):
        """``pool_batches`` global batches of float32 images (standard
        normal, as the repo has always fed them) and uniform labels."""
        rng = np.random.default_rng(seed)
        n = traffic["pool_batches"] * batch
        images = rng.standard_normal((n, size, size, 3), dtype=np.float32)
        labels = rng.integers(0, m["num_classes"], n, dtype=np.int32)
        return images, labels

    opt = dict(config["optimizer"])
    return types.SimpleNamespace(
        model=model, loss_fn=loss_fn, pool=pool,
        optimizer=getattr(optax, opt.pop("name"))(**opt),
        flops_per_step=3 * 2 * batch * forward_macs_per_image(m),
        flash=None)


def forward_macs_per_image(m):
    """Multiply-adds the forward pass of the published network requires for
    one image: the 7x7 stem (the space-to-depth stem the model runs does
    more, which is not required work), v1.5 bottlenecks (stride in the 3x3)
    and the classifier. 4.089e9 for ResNet-50 at 224x224."""
    side = m["image_size"] // 2
    macs = 7 * 7 * 3 * m["width"] * side * side
    side //= 2                                     # max pool
    c_in = m["width"]
    for stage, blocks in enumerate(m["stage_sizes"]):
        mid = m["width"] * 2 ** stage
        for block in range(blocks):
            stride = 2 if stage > 0 and block == 0 else 1
            out = side // stride
            macs += c_in * mid * side * side           # 1x1, before the stride
            macs += 9 * mid * mid * out * out          # 3x3, strided
            macs += mid * 4 * mid * out * out          # 1x1
            if block == 0:
                macs += c_in * 4 * mid * out * out     # projection
            c_in, side = 4 * mid, out
    return macs + c_in * m["num_classes"]
