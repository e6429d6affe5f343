"""The paper's Table III LSTM on HARSense-like windows: how the program
is given it, its plain reference, and its model FLOPs."""

import math

import jax
import jax.numpy as jnp

import datagen


def dataset(conf: dict, seed: int):
    m, d = conf["model"], conf["data"]
    x, y, _ = datagen.har_windows(seed, num_samples=d["num_samples"],
                                  seq_len=m["seq_len"],
                                  num_channels=m["input_dim"],
                                  num_users=d["num_users"])
    return x, y


def program_task(conf: dict):
    from repro.core import SupervisedTask
    from repro.models import LSTMClassifier, LSTMClassifierConfig
    m = conf["model"]
    return SupervisedTask(LSTMClassifier(LSTMClassifierConfig(
        input_dim=m["input_dim"], seq_len=m["seq_len"], hidden=m["hidden"],
        num_classes=m["num_classes"])), lr=conf["lr"])


class Reference:
    """One LSTM layer (gates i, f, g, o), the last hidden state into
    linear logits; weights N(0, 1/fan_in), biases 0."""

    def __init__(self, conf: dict):
        m = conf["model"]
        self.f, self.h, self.c = m["input_dim"], m["hidden"], m["num_classes"]

    def init(self, key):
        ks = jax.random.split(key, 4)
        f, h, c = self.f, self.h, self.c
        normal = lambda k, a, b: jax.random.normal(k, (a, b), jnp.float32) * (1.0 / math.sqrt(a))
        return {"wx": normal(ks[0], f, 4 * h), "wh": normal(ks[1], h, 4 * h),
                "b": jnp.zeros((4 * h,), jnp.float32),
                "w_out": normal(ks[2], h, c),
                "b_out": jnp.zeros((c,), jnp.float32)}

    def forward(self, params, x):
        def step(carry, x_t):
            h, c = carry
            z = x_t @ params["wx"] + h @ params["wh"] + params["b"]
            i, f, g, o = jnp.split(z, 4, axis=-1)
            c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
            h = jax.nn.sigmoid(o) * jnp.tanh(c)
            return (h, c), None

        zero = jnp.zeros((x.shape[0], self.h), x.dtype)
        (h, _), _ = jax.lax.scan(step, (zero, zero), jnp.moveaxis(x, 1, 0))
        return h @ params["w_out"] + params["b_out"]


def forward_flops(conf: dict) -> float:
    """FLOPs of one window's forward pass: the gate matmuls at every
    step and the head, 2 per multiply-add (gate nonlinearities, a few
    per hidden unit, are left out)."""
    m = conf["model"]
    f, h, c, t = m["input_dim"], m["hidden"], m["num_classes"], m["seq_len"]
    return float(t * 2 * (f + h) * 4 * h + 2 * h * c)
