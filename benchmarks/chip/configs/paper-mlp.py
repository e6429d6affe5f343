"""The paper's Table III MLP on the calories table: how the program is
given it, its plain reference, and its model FLOPs."""

import math

import jax
import jax.numpy as jnp

import datagen


def dataset(conf: dict, seed: int):
    return datagen.calories_table(seed, num_samples=conf["data"]["num_samples"],
                                  num_features=conf["data"]["num_features"])


def program_task(conf: dict):
    from repro.core import SupervisedTask
    from repro.models import MLPClassifier, MLPClassifierConfig
    m = conf["model"]
    return SupervisedTask(MLPClassifier(MLPClassifierConfig(
        input_dim=m["input_dim"], hidden=tuple(m["hidden"]),
        num_classes=m["num_classes"])), lr=conf["lr"])


def _dims(conf):
    m = conf["model"]
    return [m["input_dim"], *m["hidden"], m["num_classes"]]


class Reference:
    """ReLU layers, then linear logits; weights N(0, 1/fan_in), bias 0."""

    def __init__(self, conf: dict):
        self.dims = _dims(conf)

    def init(self, key):
        ks = jax.random.split(key, len(self.dims) - 1)
        return {f"layer{i}": {
            "w": jax.random.normal(ks[i], (a, b), jnp.float32) * (1.0 / math.sqrt(a)),
            "b": jnp.zeros((b,), jnp.float32)}
            for i, (a, b) in enumerate(zip(self.dims[:-1], self.dims[1:]))}

    def forward(self, params, x):
        n = len(params)
        for i in range(n):
            x = x @ params[f"layer{i}"]["w"] + params[f"layer{i}"]["b"]
            if i < n - 1:
                x = jax.nn.relu(x)
        return x


def forward_flops(conf: dict) -> float:
    """FLOPs of one sample's forward pass: 2 per multiply-add."""
    d = _dims(conf)
    return float(sum(2 * a * b for a, b in zip(d[:-1], d[1:])))
