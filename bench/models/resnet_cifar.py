"""The CIFAR ResNet family as the benchmark hands it to the program.

* ``make_params``: the weights, made by the benchmark so that the
  reference can take the same ones: read from a committed checkpoint
  with numpy, or drawn on the device from a fixed key in one jitted
  call (He-normal convs, as the configuration file states).
* ``program_workload``: the program's own ``classification`` workload
  (``fidelity=True``: rows carry ``logit_mae`` and ``accuracy``) over
  those weights, on the eval images the traffic mix asks for.
"""
from __future__ import annotations

import os

import numpy as np

from bench.references.resnet_cifar import layer_names


def _tree(flat: dict) -> dict:
    out: dict = {}
    for key, arr in flat.items():
        *path, leaf = key.split("/")
        node = out
        for p in path:
            node = node.setdefault(p, {})
        node[leaf] = arr
    return out


def init_params(cfg: dict, key):
    """He-normal weights of the configuration, batch-norm scale 1 and
    shift 0, head weights normal / sqrt(fan_in) and bias 0."""
    import jax
    import jax.numpy as jnp

    shapes = {"conv_init/w": (3, 3, cfg["in_channels"], cfg["widths"][0])}
    cin = cfg["widths"][0]
    for s, width in enumerate(cfg["widths"]):
        for b in range(cfg["n_blocks"]):
            shapes[f"s{s}_b{b}/conv1/w"] = (3, 3, cin, width)
            shapes[f"s{s}_b{b}/conv2/w"] = (3, 3, width, width)
            if cin != width:
                shapes[f"s{s}_b{b}/proj/w"] = (1, 1, cin, width)
            cin = width
    keys = jax.random.split(key, len(shapes) + 1)
    flat = {}
    for k, (name, shape) in zip(keys, shapes.items()):
        fan = shape[0] * shape[1] * shape[2]
        flat[name] = jax.random.normal(k, shape) * np.sqrt(2.0 / fan)
        if not name.endswith("proj/w"):
            base = name[:-1]
            flat[base + "bn_g"] = jnp.ones((shape[3],))
            flat[base + "bn_b"] = jnp.zeros((shape[3],))
    width, n_classes = cfg["widths"][-1], cfg["n_classes"]
    flat["head/w"] = (jax.random.normal(keys[-1], (width, n_classes))
                      / np.sqrt(width))
    flat["head/b"] = jnp.zeros((n_classes,))
    return _tree(flat)


def make_params(cfg: dict, root: str):
    """The configuration's float32 weights, on the device."""
    import jax

    w = cfg["weights"]
    if w["kind"] == "checkpoint":
        with np.load(os.path.join(root, w["path"])) as z:
            flat = {k[len(w["prefix"]):]: z[k] for k in z.files
                    if k.startswith(w["prefix"])}
        return jax.device_put(_tree(flat))
    if w["kind"] == "init":
        return jax.jit(lambda: init_params(cfg, jax.random.PRNGKey(
            w["key"])))()
    raise ValueError(f"unknown weights kind {w['kind']!r}")


def program_workload(cfg: dict, params, eval_images: int, eval_batch: int):
    """The program's classification workload of this configuration."""
    from repro.approx.workload import classification
    from repro.models.resnet import ResNetConfig

    rcfg = ResNetConfig(n_blocks=cfg["n_blocks"], widths=tuple(cfg["widths"]),
                        n_classes=cfg["n_classes"],
                        image_size=cfg["image_size"],
                        norm_eps=cfg["norm_eps"])
    wl = classification(rcfg, params, eval_n=eval_images, batch=eval_batch,
                        fidelity=True)
    if list(wl.layer_counts) != layer_names(cfg)[:-1]:
        raise ValueError(f"the program's layers {list(wl.layer_counts)} "
                         f"are not the configuration's {layer_names(cfg)}")
    return wl
