"""Plain reference of multinomial logistic regression with an l2 term,
in straightforward jax.numpy."""
import jax
import jax.numpy as jnp


def init(key, cfg, dtype=jnp.float32):
    """Weights of scale 0.01 and zero biases: {w (dim, classes), b}."""
    d = 1
    for s in cfg["input_shape"]:
        d *= s
    return {"w": (0.01 * jax.random.normal(key, (d, cfg["num_classes"])))
            .astype(dtype),
            "b": jnp.zeros((cfg["num_classes"],), dtype)}


def apply(params, x, precision):
    xf = x.reshape(x.shape[0], -1)
    return jnp.dot(xf, params["w"], precision=precision) + params["b"]


def loss(params, batch, cfg, precision):
    """Mean cross-entropy plus 0.5 * l2_reg * |params|^2."""
    logp = jax.nn.log_softmax(apply(params, batch["x"], precision)
                              .astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, batch["y"][:, None], axis=-1).mean()
    sq = sum(jnp.sum(jnp.square(a.astype(jnp.float32)))
             for a in jax.tree.leaves(params))
    return nll + 0.5 * cfg["l2_reg"] * sq
