"""How the program under test is built for a ResNet configuration."""
import numpy as np


def train_symbol(cfg):
    from mxnet_tpu.models import resnet
    return resnet.get_symbol(
        num_classes=cfg["num_classes"], num_layers=cfg["num_layers"],
        image_shape=",".join(str(s) for s in cfg["image_shape"]),
        dtype=cfg["training"]["compute_dtype"])


def train_shapes(cfg, traffic):
    import jax.numpy as jnp
    b = traffic["batch"]
    # a float32 graph has no cast at its input and takes float32 images
    dtypes = {"data": jnp.uint8} if _uint8_input(cfg) else {}
    return ({"data": (b,) + tuple(cfg["image_shape"]),
             "softmax_label": (b,)}, dtypes)


def _uint8_input(cfg):
    return cfg["training"]["compute_dtype"] != "float32"


def train_batches(cfg, traffic, seed):
    """A rotating set of host batches: uint8 NCHW images that all differ and
    their labels (float32, the type the program's graph takes)."""
    rs = np.random.default_rng([int(seed), 1])
    b = traffic["batch"]
    image_dtype = np.uint8 if _uint8_input(cfg) else np.float32
    return [{"data": rs.integers(0, 256, (b,) + tuple(cfg["image_shape"]),
                                 dtype=np.uint8).astype(image_dtype),
             "softmax_label": rs.integers(0, cfg["num_classes"], b)
             .astype(np.float32)}
            for _ in range(traffic["rotating_batches"])]


def work_per_step(cfg, traffic):
    from benchmark.lib import flops
    return {"flops": flops.resnet_train_flops_per_step(cfg, traffic["batch"]),
            "items": traffic["batch"]}
