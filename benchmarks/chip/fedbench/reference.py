"""The plain reference of a federated run: the cell's model family
(``models/<family>.py``) and uplink method (``methods/<method>.py``), driven
round by round from the seed, as the configuration states the round:

  each round the seed's selection chain picks ``n_sel`` clients; each client
  takes ``local_steps`` SGD steps (lr, f32 arithmetic, weights rounded back to
  the configuration's dtype) on its own next batches from the global
  weights; its update is its weights minus the global ones; the method turns
  the round's updates into one mean update, which the server adds to the
  global weights (server lr 1); at every ``eval_every``-th round the held-out
  loss is taken over the eval block.

"""

from __future__ import annotations

import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

from . import data

HERE = pathlib.Path(__file__).resolve().parents[1]
F32 = jnp.float32


def load(kind: str, name: str):
    """``<benchmark dir>/<kind>/<name>.py`` as a module."""
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} module {path}")
    spec = importlib.util.spec_from_file_location(f"fedbench_{kind}_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def groups_of(params) -> dict:
    """{group: (per-layer shape, stack)}: each leaf of ``layers`` is a stack
    over the layers, every other leaf one group of its own."""
    out = {}
    for name, leaf in params.items():
        if name == "layers":
            for sub, x in leaf.items():
                out[f"layers/{sub}"] = (tuple(x.shape[1:]), int(x.shape[0]))
        else:
            out[name] = (tuple(leaf.shape), 1)
    return out


def get(params, group):
    node = params
    for part in group.split("/"):
        node = node[part]
    return node


def with_groups(params, new: dict):
    out = {k: (dict(v) if isinstance(v, dict) else v) for k, v in params.items()}
    for group, x in new.items():
        parts = group.split("/")
        node = out
        for part in parts[:-1]:
            node = node[part]
        node[parts[-1]] = x
    return out


class Run:
    """One reference run of a cell from a seed, kept on the device."""

    def __init__(self, model_cfg: dict, traffic: dict, seed: int, cd=None):
        self.cfg, self.traffic, self.seed = model_cfg, traffic, seed
        self.cd = jnp.dtype(cd or model_cfg["dtype"])
        self.model = load("models", model_cfg["family"])
        self.method = load("methods", traffic["method"])
        self.task = data.Task(model_cfg["vocab"], traffic["n_clients"],
                              traffic["alpha"], seed)
        self.streams = {}
        self.n_sel = data.n_selected(traffic["participation"],
                                     traffic["n_clients"])
        # op by op, as the program draws them: under jit XLA may skip the
        # rounding of a draw to bf16 before its scale
        self.params = self.model.init(model_cfg, jax.random.PRNGKey(seed))
        self.groups = groups_of(self.params)
        self.uplink = self.method.Uplink(self.groups, seed,
                                         traffic["n_clients"], traffic)
        self.evals = data.eval_block(self.task, traffic["batch"],
                                     traffic["seq"], seed,
                                     traffic["eval_batches"])
        self._train = jax.jit(self._local_train)
        self._eval = jax.jit(self._eval_loss)

    def _local_train(self, params, batches):
        cfg, cd, lr = self.cfg, self.cd, self.traffic["lr"]

        def step(p, b):
            g = jax.grad(lambda q: self.model.loss(cfg, q, b, cd))(p)
            return jax.tree.map(
                lambda w, gw: (w.astype(F32) - lr * gw.astype(F32))
                .astype(w.dtype), p, g), None

        return jax.lax.scan(step, params, batches)[0]

    def _eval_loss(self, params, block):
        return jnp.mean(jax.lax.map(
            lambda b: self.model.eval_loss(self.cfg, params, b, self.cd),
            block))

    def _batches(self, client: int):
        t = self.traffic
        if client not in self.streams:
            self.streams[client] = self.task.stream(
                client, t["batch"], t["seq"], self.seed)
        bs = [next(self.streams[client]) for _ in range(t["local_steps"])]
        return {k: jnp.stack([b[k] for b in bs]) for k in bs[0]}

    def round(self, rnd: int) -> None:
        sel = data.selected_clients(self.seed, rnd, self.traffic["n_clients"],
                                    self.n_sel)
        deltas = {g: [] for g in self.groups}
        with jax.default_matmul_precision("highest"):
            for c in sel:
                local = self._train(self.params, self._batches(c))
                for g in self.groups:
                    deltas[g].append(get(local, g) - get(self.params, g))
                del local
            mean = self.uplink.round(deltas, sel)
        del deltas
        self.params = with_groups(self.params, {
            g: get(self.params, g) + mean[g].astype(get(self.params, g).dtype)
            for g in self.groups})

    def eval_loss(self) -> float:
        with jax.default_matmul_precision("highest"):
            return float(self._eval(self.params, self.evals))


def leaf_norms(params, base) -> dict:
    """{leaf path: ||params - base||} in f64 on the host."""
    out = {}
    for g in groups_of(base):
        a = np.asarray(get(params, g), np.float64)
        b = np.asarray(get(base, g), np.float64)
        out[g] = float(np.linalg.norm((a - b).ravel()))
    return out
