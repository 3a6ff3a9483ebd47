"""Plain reference for the ResNet family: forward pass, cross-entropy loss,
gradients and update in straightforward ``jax.numpy``/``lax`` and float32,
convolutions at ``highest`` precision. No flax module, nothing of the library
under test; the flax parameter tree is read by name.

Follows He et al. (arXiv:1512.03385, Table 1) in its v1.5 form (the stride
sits in each stage's first 3x3). Departure, the model's (configs/
resnet50.json "assumed"): the stem is the space-to-depth form, a 4x4 stride-1
convolution over the image folded to 112x112x12, because that is the weight
the system holds. BatchNorm normalises with the statistics of the WHOLE
global batch, which is what sync-BatchNorm promises; the batch is split over
the cell's devices by ``jax.jit``'s own shardings, which leaves the
mathematics that of one batch. Each block is checkpointed for memory.
"""


def program(config, devices):
    """``init(params) -> state`` and the jitted ``step(params, state,
    batch) -> (params, state, loss)``. Trace the step under
    ``jax.default_matmul_precision("highest")``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    m = config["model"]
    opt = dict(config["optimizer"])
    optimizer = getattr(optax, opt.pop("name"))(**opt)

    def conv(x, p, name, stride=1, padding="SAME"):
        return jax.lax.conv_general_dilated(
            x, p[name + "/kernel"], (stride, stride), padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    def norm(x, p, name):
        mean = x.mean((0, 1, 2))
        var = ((x - mean) ** 2).mean((0, 1, 2))
        return (x - mean) / jnp.sqrt(var + 1e-5) * p[name + "/scale"] \
            + p[name + "/bias"]

    def block(x, p, stride):
        y = jax.nn.relu(norm(conv(x, p, "Conv_0"), p, "BatchNorm_0"))
        y = jax.nn.relu(norm(conv(y, p, "Conv_1", stride), p, "BatchNorm_1"))
        y = norm(conv(y, p, "Conv_2"), p, "BatchNorm_2")
        if "conv_proj/kernel" in p:
            x = norm(conv(x, p, "conv_proj", stride), p, "norm_proj")
        return jax.nn.relu(x + y)

    def loss_of(p, images, labels):
        n, h, w, c = images.shape
        x = images.reshape(n, h // 2, 2, w // 2, 2, c)
        x = x.transpose(0, 1, 3, 2, 4, 5).reshape(n, h // 2, w // 2, 4 * c)
        x = conv(x, p, "conv_init_s2d", padding=[(1, 2), (1, 2)])
        x = jax.nn.relu(norm(x, p, "bn_init"))
        x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                                  (1, 2, 2, 1), "SAME")
        i = 0
        for stage, blocks in enumerate(m["stage_sizes"]):
            for j in range(blocks):
                prefix = f"BottleneckResNetBlock_{i}/"
                sub = {k[len(prefix):]: v for k, v in p.items()
                       if k.startswith(prefix)}
                stride = 2 if stage > 0 and j == 0 else 1
                x = jax.checkpoint(block, static_argnums=2)(x, sub, stride)
                i += 1
        logits = x.mean((1, 2)) @ p["Dense_0/kernel"] + p["Dense_0/bias"]
        nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, labels[:, None], -1)[:, 0]
        return nll.mean()

    def step(p, state, batch):
        loss, grads = jax.value_and_grad(loss_of)(p, *batch)
        updates, state = optimizer.update(grads, state, p)
        return optax.apply_updates(p, updates), state, loss

    mesh = Mesh(np.array(devices), ("batch",))
    whole, split = NamedSharding(mesh, P()), NamedSharding(mesh, P("batch"))
    return jax.jit(optimizer.init, out_shardings=whole), jax.jit(
        step, in_shardings=(whole, whole, split), out_shardings=whole,
        donate_argnums=(0, 1))
