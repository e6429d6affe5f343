"""The benchmark's own traffic generators.

Copies of the program's synthetic data (``repro.data.har``,
``repro.data.partition``) and of its neighbour-device draw
(``repro.core.incentive.make_fleet``), kept here so that a change to the
program cannot move the yardstick.  ``tests/test_datagen.py`` shows that
each copy equals the program's output for seed 0.
"""

from __future__ import annotations

import numpy as np

HAR_ACTIVITIES = 6
# per-activity signature: (base freq, amplitude, gravity-axis offset, harmonic amp)
_ACT_SIG = {
    0: (2.6, 2.0, 0.4, 0.8),    # Running
    1: (1.4, 1.0, 0.4, 0.4),    # Walking
    2: (0.05, 0.05, -0.9, 0.0),  # Sitting
    3: (0.05, 0.05, 1.0, 0.0),   # Standing
    4: (1.7, 1.3, 0.1, 0.6),    # Downstairs
    5: (1.2, 1.5, 0.7, 0.3),    # Upstairs
}


def har_windows(seed: int, num_samples: int = 6000, seq_len: int = 64,
                num_channels: int = 6, num_users: int = 12,
                noise: float = 0.35):
    """HARSense-like windows: x (N, T, C) fp32, y (N,) int32, user (N,)."""
    rng = np.random.default_rng(seed)
    n, t_len, c = num_samples, seq_len, num_channels
    y = rng.integers(0, HAR_ACTIVITIES, size=n)
    user = rng.integers(0, num_users, size=n)
    user_gain = rng.normal(1.0, 0.12, size=num_users)
    user_freq = rng.normal(1.0, 0.08, size=num_users)
    t = np.arange(t_len)[None, :, None] / 20.0          # 20 Hz sampling
    phase = rng.uniform(0, 2 * np.pi, size=(n, 1, c))
    chan_mix = rng.normal(1.0, 0.2, size=(1, 1, c))
    sig = np.array([_ACT_SIG[k] for k in range(HAR_ACTIVITIES)])[y]
    freq, amp, grav, harm = (sig[:, k][:, None, None] for k in range(4))
    freq = freq * user_freq[user][:, None, None]
    amp = amp * user_gain[user][:, None, None]
    x = amp * np.sin(2 * np.pi * freq * t + phase) * chan_mix
    x = x + harm * np.sin(2 * np.pi * 2 * freq * t + 2 * phase)
    x[:, :, 0::3] += grav
    x = x + rng.normal(0, noise, size=x.shape)
    return x.astype(np.float32), y.astype(np.int32), user.astype(np.int32)


def calories_table(seed: int, num_samples: int = 5000, num_features: int = 8,
                   noise: float = 0.10, cal_noise: float = 0.04):
    """Calories-burned table: x (N, 8) fp32, y (N,) int32 in 5 classes
    (kcal/min bins <0.5, 0.5-1, 1-2, 2-3, >3 by the MET formula)."""
    rng = np.random.default_rng(seed)
    n = num_samples
    intensity = rng.gamma(2.0, 0.8, size=n)
    duration = rng.uniform(0.2, 1.5, size=n)
    weight = rng.normal(75, 12, size=n)
    cal_per_min = intensity * weight * 3.5 / 200.0
    y = np.digitize(cal_per_min, np.array([0.5, 1.0, 2.0, 3.0]))
    x = np.zeros((n, num_features), np.float32)
    x[:, 0] = intensity + rng.normal(0, noise, n)
    x[:, 1] = duration + rng.normal(0, noise * 0.3, n)
    x[:, 2] = (weight - 75) / 12 + rng.normal(0, noise, n)
    x[:, 3] = intensity * duration + rng.normal(0, noise * 2, n)
    x[:, 4] = np.log1p(intensity) + rng.normal(0, noise, n)
    x[:, 5] = rng.normal(0, 1, n)
    x[:, 6] = cal_per_min + rng.normal(0, cal_noise, n)
    x[:, 7] = rng.normal(25, 4, n) / 10
    return x.astype(np.float32), y.astype(np.int32)


def dirichlet_split(y: np.ndarray, num_clients: int, alpha: float, seed: int,
                    min_per_client: int = 8):
    """Sample indices per client with Dirichlet(alpha) label skew."""
    rng = np.random.default_rng(seed)
    idx_by_class = [np.flatnonzero(y == c) for c in np.unique(y)]
    for idx in idx_by_class:
        rng.shuffle(idx)
    client_idx = [[] for _ in range(num_clients)]
    for idx in idx_by_class:
        props = rng.dirichlet(np.full(num_clients, alpha))
        cuts = (np.cumsum(props) * len(idx)).astype(int)[:-1]
        for cid, part in enumerate(np.split(idx, cuts)):
            client_idx[cid].extend(part.tolist())
    out = []
    pool = np.arange(len(y))
    for cid in range(num_clients):
        arr = np.asarray(client_idx[cid], dtype=np.int64)
        if len(arr) < min_per_client:
            extra = rng.choice(pool, size=min_per_client - len(arr),
                               replace=False)
            arr = np.concatenate([arr, extra])
        rng.shuffle(arr)
        out.append(arr)
    return out


def neighbour_draws(num_devices: int, seed: int, p_has_model: float = 0.9):
    """The nearby devices' attributes, one dict per device, in the order
    and from the draws of ``repro.core.incentive.make_fleet``."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(num_devices):
        out.append(dict(
            device_id=i,
            battery_level=float(rng.uniform(0.15, 1.0)),
            model_staleness=float(rng.exponential(1.0)),
            data_size=int(rng.integers(200, 2000)),
            reservation_price=float(rng.uniform(0.2, 1.0)),
            has_model=bool(rng.random() < p_has_model)))
    return out


def fixed_size(idx: np.ndarray, n: int) -> np.ndarray:
    """The first ``n`` of a client's indices, repeated cyclically where
    the client holds fewer, so that every seed gives the same shapes."""
    return np.resize(idx, n)
