"""Plain reference of one static EnFed study (arXiv:2412.00768, Alg. 1).

Written from the protocol, in straightforward ``jax.numpy``: no kernel,
no flat round state, no refresh dedup, nothing imported from the
program and nothing taken from what it made.  It builds its own
contributors from the seed (initialisation and pre-training), signs its
own contracts, and runs each compared session round by round:

1. aggregate: the requester averages its signed contributors' models
   (eq. 14, every weight 1, summed in contract order);
2. fit: it trains that average on its own shard for E epochs of Adam;
3. score: it evaluates the result on its test split and stops once the
   desired accuracy is reached, or after ``max_rounds`` rounds;
4. refresh: every contributor trains one more epoch on its own shard.

The minibatch order is the protocol's counter-based schedule: per epoch,
the stable argsort of a threefry hash of each sample index under
``fold_in(PRNGKey(seed), epoch)``; drop-last batches; requester fits in
round r use ``seed + r``, contributor refreshes ``seed + device_id``,
pre-training of contributor j seed j from ``PRNGKey(10 + j)``.

``dtype`` sets the precision of the whole computation: the configuration
states float32; ``bfloat16`` is the control that has to fail the
comparison.  Battery and energy are not modelled: the configurations'
batteries outlast ``max_rounds`` (their files say so under ``assumed``),
so the reference's stop rule is accuracy or the round budget.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

B1, B2, EPS = 0.9, 0.999, 1e-8


def schedule(seed: int, epochs: int, n: int, batch: int):
    """(epochs * steps, batch) sample indices and 0/1 weights."""
    base = jax.random.PRNGKey(seed)
    pos = jnp.arange(n, dtype=jnp.uint32)

    def scores(e):
        k = jax.random.fold_in(base, e)
        return jax.vmap(lambda i: jax.random.bits(
            jax.random.fold_in(k, i), (), jnp.uint32))(pos)

    s = jax.vmap(scores)(jnp.arange(epochs, dtype=jnp.uint32))
    perm = jnp.argsort(s, axis=-1, stable=True).astype(jnp.int32)
    steps = max(n // batch, 1)
    take = steps * batch
    if take > n:
        perm = jnp.pad(perm, ((0, 0), (0, take - n)))
    used = (n // batch) * batch if n >= batch else n
    w = (jnp.arange(take) < used).astype(jnp.float32)
    idx = jnp.where(w > 0, perm[:, :take], 0)
    return (idx.reshape(epochs * steps, batch),
            jnp.broadcast_to(w.reshape(1, steps, batch),
                             (epochs, steps, batch)).reshape(-1, batch))


def cross_entropy(logits, y, w):
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]
    return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)


def make_fit(forward, lr: float, dtype):
    """fit(params, x, y, idx, w) -> params: Adam from a fresh state over
    the given minibatch schedule."""
    lr = jnp.asarray(lr, dtype)

    def fit(params, x, y, idx, w):
        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)

        def step(carry, iw):
            p, m, v, t = carry
            ib, wb = iw
            g = jax.grad(lambda q: cross_entropy(
                forward(q, x[ib]), y[ib], wb.astype(dtype)))(p)
            t = t + 1
            m = jax.tree_util.tree_map(lambda a, b: B1 * a + (1 - B1) * b, m, g)
            v = jax.tree_util.tree_map(
                lambda a, b: B2 * a + (1 - B2) * (b * b), v, g)
            bc1 = (1 - B1 ** t.astype(jnp.float32)).astype(dtype)
            bc2 = (1 - B2 ** t.astype(jnp.float32)).astype(dtype)
            new = jax.tree_util.tree_map(
                lambda q, a, b: q - lr * (a / bc1) / (jnp.sqrt(b / bc2) + EPS),
                p, m, v)
            take = jnp.sum(wb) > 0
            keep = lambda a, b: jax.tree_util.tree_map(
                lambda u, z: jnp.where(take, u, z), a, b)
            return (keep(new, p), keep(m, carry[1]), keep(v, carry[2]), t), None

        (p, _, _, _), _ = jax.lax.scan(
            step, (params, zeros, zeros, jnp.int32(0)), (idx, w))
        return p

    return fit


def utility(dev: dict, max_data: int) -> float:
    """The contract utility the requester ranks agreeing devices by."""
    fresh = 1.0 / (1.0 + dev["model_staleness"])
    return (0.5 * fresh + 0.3 * dev["data_size"] / max(max_data, 1)
            + 0.2 * min(dev["battery_level"] / 0.5, 1.0))


def sign(devices, offered: float, n_max: int, min_battery: float = 0.1):
    """Indices of the signed devices, best contract first."""
    agree = [j for j, d in enumerate(devices)
             if d["has_model"] and d["battery_level"] >= min_battery
             and offered >= d["reservation_price"]]
    max_data = max((devices[j]["data_size"] for j in agree), default=1)
    return sorted(agree, key=lambda j: -utility(devices[j], max_data))[:n_max]


# the protocol knobs this reference models; a traffic mix that sets any
# other (compression, faults, cadence, adversaries, robust statistics,
# strategies, battery thresholds) needs the reference extended first
MODELLED = {"max_rounds", "epochs", "batch_size", "n_max",
            "contributor_refresh_epochs", "desired_accuracy",
            "offered_incentive"}


def study(model, world, conf: dict, traffic: dict, sessions, dtype=jnp.float32):
    """The reference outcome of ``sessions`` (requester indices) in one
    study of ``world``: per session its final params (a numpy pytree),
    executed rounds, stop reason, number of signed contributors, and its
    test accuracy after each round (and the last as ``final_accuracy``)
    over the ``test_rows`` rows of the test split."""
    knobs = traffic["method"]
    if set(knobs) != MODELLED:
        raise ValueError(f"the reference models exactly the knobs "
                         f"{sorted(MODELLED)}; the traffic sets {sorted(knobs)}")
    cast = lambda t: jax.tree_util.tree_map(lambda a: jnp.asarray(a, dtype), t)
    fit = jax.jit(make_fit(model.forward, conf["lr"], dtype))
    fit_many = jax.jit(jax.vmap(make_fit(model.forward, conf["lr"], dtype),
                                in_axes=(0, 0, 0, None, None)))
    refresh = jax.jit(jax.vmap(make_fit(model.forward, conf["lr"], dtype)))
    batch = knobs["batch_size"]

    # contributors: initialised and pre-trained from the seed
    contrib = []
    for j, (x, y) in enumerate(world.shards):
        idx, w = schedule(j, conf["pretrain_epochs"], len(x), batch)
        contrib.append(fit(cast(model.init(jax.random.PRNGKey(10 + j))),
                           jnp.asarray(x, dtype), jnp.asarray(y), idx, w))
    signed = sign(world.devices, knobs["offered_incentive"], knobs["n_max"])
    cx = jnp.stack([jnp.asarray(world.shards[j][0], dtype) for j in signed])
    cy = jnp.stack([jnp.asarray(world.shards[j][1]) for j in signed])
    c = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *[contrib[j] for j in signed])
    n_c = len(world.shards[0][0])

    sessions = list(sessions)
    ox = jnp.stack([jnp.asarray(world.own_train[i][0], dtype) for i in sessions])
    oy = jnp.stack([jnp.asarray(world.own_train[i][1]) for i in sessions])
    tx = jnp.asarray(world.own_test[0], dtype)
    ty = jnp.asarray(world.own_test[1])
    accuracy = jax.jit(jax.vmap(lambda p: jnp.mean(
        (jnp.argmax(model.forward(p, tx), -1) == ty).astype(jnp.float32))))

    active = np.ones(len(sessions), bool)
    rounds = np.zeros(len(sessions), np.int64)
    reason = ["max_rounds"] * len(sessions)
    history = [[] for _ in sessions]
    final = None
    for r in range(knobs["max_rounds"]):
        glob = jax.tree_util.tree_map(lambda a: jnp.sum(a, 0) / len(signed), c)
        start = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (len(sessions),) + a.shape), glob)
        idx, w = schedule(world.seed + r, knobs["epochs"], ox.shape[1], batch)
        fitted = fit_many(start, ox, oy, idx, w)
        acc = np.asarray(accuracy(fitted))
        for k in np.flatnonzero(active):
            history[k].append(float(acc[k]))
        final = fitted if final is None else jax.tree_util.tree_map(
            lambda new, old: jnp.where(
                jnp.asarray(active).reshape((-1,) + (1,) * (new.ndim - 1)),
                new, old), fitted, final)
        rounds += active
        for k in np.flatnonzero(active & (acc >= knobs["desired_accuracy"])):
            reason[k] = "accuracy_reached"
        active &= acc < knobs["desired_accuracy"]
        if not active.any():
            break
        seeds = [world.seed + signed[k] for k in range(len(signed))]
        plans = [schedule(s, knobs["contributor_refresh_epochs"], n_c, batch)
                 for s in seeds]
        c = refresh(c, cx, cy, jnp.stack([p[0] for p in plans]),
                    jnp.stack([p[1] for p in plans]))
    final = jax.device_get(jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), final))
    return [dict(params=jax.tree_util.tree_map(lambda a: a[k], final),
                 rounds=int(rounds[k]), stop_reason=reason[k],
                 members=len(signed), accuracy=history[k],
                 final_accuracy=history[k][-1], test_rows=len(ty))
            for k in range(len(sessions))]
