"""One static EnFed world, drawn from a seed: the data every party holds,
the nearby devices, and what each requester owns.

``draw_world`` makes plain numpy arrays and device attributes, which the
plain reference reads directly.  ``program_world`` turns them into the
program's ``repro.api.WorldSpec``: the program's own task, its
loop-engine pre-training of the contributors, and one ``RequesterSpec``
per requester.  The logic follows the world of ``chip_smoke.build_world``
(Dirichlet split over requester + contributors, pre-trained
contributors, requester shards sampled from one pool), with every size
fixed by the configuration so that each seed gives the same shapes and
so reuses the same compiled programs.
"""

from __future__ import annotations

import dataclasses
from typing import List

import numpy as np

import datagen

# the world seed enters the program as an int32 (``EnFedConfig.seed``,
# plus round numbers and device ids): keep it well inside that range
WORLD_SEED_MOD = 1 << 30


@dataclasses.dataclass
class World:
    seed: int                     # the world seed the program is given
    shards: List[tuple]           # contributor j's (x, y), j = 0..N-1
    devices: List[dict]           # contributor j's attributes (device_id j)
    own_train: List[tuple]        # requester i's private shard
    own_test: tuple               # the requesters' common test split


def world_seed(seed: int) -> int:
    return int(seed) % WORLD_SEED_MOD


def draw_world(conf: dict, traffic: dict, data: tuple, seed: int) -> World:
    """The world of ``traffic["requesters"]`` requesters and
    ``traffic["contributors"]`` contributors, from ``seed``."""
    ws = world_seed(seed)
    x, y = data[0], data[1]
    n_c = traffic["contributors"]
    parts = datagen.dirichlet_split(y, n_c + 1, alpha=conf["alpha"], seed=ws)
    shards = []
    for p in parts[1:]:
        sel = datagen.fixed_size(p, conf["shard_samples"])
        shards.append((x[sel], y[sel]))
    pool = datagen.fixed_size(parts[0],
                              conf["requester_pool"] + conf["test_samples"])
    train_pool, test = pool[:conf["requester_pool"]], pool[conf["requester_pool"]:]
    devices = datagen.neighbour_draws(n_c, seed=ws + 1, p_has_model=1.0)
    for d in devices:
        d["reservation_price"] = conf["reservation_price"]
    rng = np.random.default_rng(ws)
    own = []
    for _ in range(traffic["requesters"]):
        sel = train_pool[rng.permutation(len(train_pool))[:traffic["own_samples"]]]
        own.append((x[sel], y[sel]))
    return World(seed=ws, shards=shards, devices=devices, own_train=own,
                 own_test=(x[test], y[test]))


def program_world(task, world: World, conf: dict, traffic: dict):
    """The program's ``WorldSpec`` for ``world``: contributors
    pre-trained by the program's loop engine (``task.fit``), as a user
    of the program builds a world."""
    from repro.api import WorldSpec
    from repro.core import RequesterSpec
    from repro.core.incentive import NeighborDevice

    fleet = [NeighborDevice(**d) for d in world.devices]
    states = {}
    for j, dev in enumerate(fleet):
        params = task.init(seed=10 + j)
        params, _ = task.fit(params, world.shards[j],
                             epochs=conf["pretrain_epochs"],
                             batch_size=traffic["method"]["batch_size"],
                             seed=j)
        states[dev.device_id] = {"params": params, "data": world.shards[j]}
    requesters = [RequesterSpec(own_train=own, own_test=world.own_test,
                                neighborhood=fleet, contributor_states=states)
                  for own in world.own_train]
    return WorldSpec(task=task, requesters=requesters, seed=world.seed)


def method_spec(traffic: dict):
    """The program's ``MethodSpec`` for a traffic mix: EnFed with the
    mix's ``method`` knobs as plain values, every other knob at its
    default."""
    from repro.api import MethodSpec
    return MethodSpec(name="enfed", **traffic["method"])
