"""What the drivers share: the program's configuration and data of a cell,
and one Map+Reduce job through the program's own entry."""
from __future__ import annotations

import jax
import numpy as np
from repro.configs.base import get_config, replace
from repro.core.runner import AveragingRun, MapConfig, ReduceConfig
from repro.data.partition import Partition
from repro.launch.mesh import make_member_mesh
from repro.optim.schedules import dynamic_paper

from chipbench import data
from chipbench.harness import job_seeds


def program_config(env):
    """The program's ``ArchConfig`` for the configuration file: its named
    architecture with the file's model sizes."""
    fields = {k: (tuple(v) if isinstance(v, list) else v)
              for k, v in env.model().items()}
    return replace(get_config(env.config["arch"]), **fields)


def partitions(env):
    """The cell's k partitions as host arrays [(x, y), ...]."""
    d, t = env.config["data"], env.traffic
    x, y = data.make(d["generator"], d["n_per_class"], d["seed"],
                     env.data_cache())
    parts = data.partition(x, y, t["members"], t["partition"], d["seed"])
    del x, y
    return parts


def job_spec(env) -> dict:
    t = env.traffic
    return dict(epochs=t["epochs"], rounds=t.get("rounds", 1), lr=t["lr"],
                batch=t["batch"])


class Jobs:
    """Map+Reduce jobs through ``AveragingRun.run``; job j trains from the
    init and batch order that ``--seed`` and j give."""

    def __init__(self, env, parts):
        self.env, self.cfg = env, program_config(env)
        self.parts = [Partition(x, y) for x, y in parts]
        s = job_spec(env)
        mesh = make_member_mesh() if env.traffic["backend"] == "mesh" \
            else None
        self._map = dict(
            epochs=s["epochs"], batch_size=s["batch"],
            backend=env.traffic["backend"], mesh=mesh,
            lr_schedule=dynamic_paper(s["lr"]) if s["epochs"] else None)
        self._reduce = ReduceConfig(rounds=s["rounds"])

    def run(self, j: int):
        """Job j, ended by ``block_until_ready`` on the members and the
        averaged model; returns those device arrays."""
        init_seed, shuffle_seed = job_seeds(self.env.seed, j)
        res = AveragingRun(self.cfg, MapConfig(seed=shuffle_seed,
                                               **self._map),
                           self._reduce).run(self.parts,
                                             jax.random.PRNGKey(init_seed))
        out = {"members": {"cnn": res.stacked.cnn_params,
                           "beta": res.stacked.beta},
               "averaged": {"cnn": res.averaged.cnn_params,
                            "beta": res.averaged.beta}}
        jax.block_until_ready(out)
        return out


def to_host(tree):
    return jax.tree.map(np.asarray, tree)
