"""Plain reference of the paper CNN: straightforward jax.numpy, each 3x3
convolution as the sum of its nine shifted matrix products (not the
program's single im2col product), at the matmul precision the caller
gives (HIGHEST for the reference).

Not ``lax.conv_general_dilated``: vmapped over 8 x 32 devices' own
weights it becomes a convolution of 256 feature groups, which on a TPU
v5e at HIGHEST precision moves the first layer's change by 8% of its
norm over one call from what the same arithmetic gives at default
precision, in the program, and on the CPU (PERF.md, PR 12)."""
import jax
import jax.numpy as jnp
from jax import lax


def init(key, cfg, dtype=jnp.float32):
    """He-normal weights and zero biases, in the program's tree layout:
    conv<i>/{w (3, 3, cin, cout), b}, dense<j>/{w (din, dout), b}."""
    h, w, c = cfg["input_shape"]
    chans = [c] + list(cfg["conv_channels"])
    dims = [(h // 4) * (w // 4) * chans[-1]] + list(cfg["hidden"]) + \
        [cfg["num_classes"]]
    keys = jax.random.split(key, len(chans) - 1 + len(dims) - 1)
    p = {}
    for i in range(len(chans) - 1):
        shape = (3, 3, chans[i], chans[i + 1])
        p[f"conv{i}"] = {
            "w": (jax.random.normal(keys[i], shape)
                  * jnp.sqrt(2.0 / (9 * chans[i]))).astype(dtype),
            "b": jnp.zeros((chans[i + 1],), dtype)}
    for j in range(len(dims) - 1):
        k = keys[len(chans) - 1 + j]
        p[f"dense{j}"] = {
            "w": (jax.random.normal(k, (dims[j], dims[j + 1]))
                  * jnp.sqrt(2.0 / dims[j])).astype(dtype),
            "b": jnp.zeros((dims[j + 1],), dtype)}
    return p


def conv3x3(h, w, precision):
    """SAME 3x3 cross-correlation: h (B, H, W, C), w (3, 3, C, O)."""
    hp = jnp.pad(h, ((0, 0), (1, 1), (1, 1), (0, 0)))
    height, width = h.shape[1], h.shape[2]
    out = 0.0
    for dy in range(3):
        for dx in range(3):
            out = out + jnp.einsum(
                "bhwc,co->bhwo", hp[:, dy:dy + height, dx:dx + width],
                w[dy, dx], precision=precision)
    return out


def apply(params, x, precision):
    """x: (B, H, W, C) -> logits (B, classes)."""
    h = x
    i = 0
    while f"conv{i}" in params:
        h = jax.nn.relu(conv3x3(h, params[f"conv{i}"]["w"], precision)
                        + params[f"conv{i}"]["b"])
        h = lax.reduce_window(h, -jnp.inf, lax.max, (1, 2, 2, 1),
                              (1, 2, 2, 1), "VALID")
        i += 1
    h = h.reshape(h.shape[0], -1)
    j = 0
    while f"dense{j}" in params:
        h = jnp.dot(h, params[f"dense{j}"]["w"], precision=precision) \
            + params[f"dense{j}"]["b"]
        if f"dense{j + 1}" in params:
            h = jax.nn.relu(h)
        j += 1
    return h


def loss(params, batch, cfg, precision):
    """Mean cross-entropy over the batch."""
    logp = jax.nn.log_softmax(apply(params, batch["x"], precision)
                              .astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, batch["y"][:, None], axis=-1).mean()
