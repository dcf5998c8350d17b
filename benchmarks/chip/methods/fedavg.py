"""Plain reference of the FedAvg uplink: every client ships its whole update
raw (f32 on the wire), and the server averages the updates of the round's
clients."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np


class Uplink:
    def __init__(self, groups: dict, seed: int, n_clients: int, cfg: dict):
        self.groups = groups

    def round(self, deltas: dict, sel: list) -> dict:
        return {g: jnp.sum(jnp.stack(ds), 0) / len(ds)
                for g, ds in deltas.items()}


def round_bits(groups: dict, cfg: dict, stats: dict, n_sel: int,
               n_upd: dict) -> int:
    return 32 * n_sel * sum(int(np.prod(s)) * L for s, L in groups.values())
