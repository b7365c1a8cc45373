"""Configurations as the program builds them, checked against the files.

A configuration file names its ``builder``: ``lm`` for the repo's LM
configs (``repro.configs``), ``image`` for the paper's image models
(``repro.models.cnn`` through ``repro.experiment``).  Every size in the
file that the program also states must agree with it, and the packed
parameter count ``packed_params`` must match, so a later change to the
program's configuration cannot move a cell unseen.
"""
from __future__ import annotations

import dataclasses
import math


def _check(cfg: dict, program: dict, what: str) -> None:
    bad = {k: (cfg[k], program[k]) for k in cfg
           if k in program and cfg[k] != program[k]}
    if bad:
        raise ValueError(f"{what}: configuration file and program disagree "
                         f"(file, program): {bad}")


def lm_config(cfg: dict):
    from repro.configs import get_config, smoke_config

    mc = (smoke_config if cfg.get("smoke") else get_config)(cfg["arch"])
    program = {k: (list(v) if isinstance(v, tuple) else v)
               for k, v in dataclasses.asdict(mc).items() if k != "name"}
    program["padded_vocab"] = mc.padded_vocab
    _check(cfg, program, cfg["name"])
    return mc


def image_model(cfg: dict):
    from repro.data.synthetic import make_image_dataset
    from repro.models.cnn import MODELS

    _, _, meta = make_image_dataset(cfg["dataset"], 1, 1)
    _check(cfg, {"image_size": meta["img"], "in_channels": meta["channels"],
                 "num_classes": meta["n_classes"]}, cfg["name"])
    return MODELS[cfg["model"]](num_classes=cfg["num_classes"],
                                in_channels=cfg["in_channels"])


def param_shapes(cfg: dict):
    """The program's parameter tree as ShapeDtypeStructs, with the packed
    count checked against ``packed_params``."""
    import jax

    if cfg["builder"] == "lm":
        from repro.models import build_model
        model = build_model(lm_config(cfg))
    elif cfg["builder"] == "image":
        model = image_model(cfg)
    else:
        raise ValueError(f"unknown builder {cfg['builder']!r}")
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    p = sum(math.prod(x.shape) for x in jax.tree.leaves(shapes))
    if "packed_params" in cfg and p != cfg["packed_params"]:
        raise ValueError(f"{cfg['name']}: the program packs {p} parameters, "
                         f"the file says {cfg['packed_params']}")
    return shapes


def loop_parts(cfg: dict, traffic: dict):
    """The program's own FL set-up of a loop cell, by builder, drawn from
    ``traffic['fleet_seed']``: (clients, eval_fn, FLConfig).  The model's
    parameter tree is ``param_shapes``'s, checked against the file.

    ``image`` builds through ``experiment.build_experiment`` (``fl`` holds
    FLConfig fields, ``data`` the data set's sizes); ``lm`` through
    ``launch.train.build_lm_fl`` (``fl`` holds its keyword arguments)."""
    fleet = traffic["fleet_seed"]
    if cfg["builder"] == "image":
        from repro.core.server import FLConfig
        from repro.experiment import ExperimentConfig, build_experiment
        from repro.runtime.simulator import SimConfig

        image_model(cfg)
        fl = FLConfig(**traffic["fl"], seed=fleet)
        sim, _, _ = build_experiment(ExperimentConfig(
            dataset=cfg["dataset"], model=cfg["model"], fl=fl,
            sim=SimConfig(**traffic["sim"], seed=fleet),
            eval_every=traffic["eval_every"], seed=fleet, **traffic["data"]))
        return sim.clients, sim.eval_fn, fl
    if cfg["builder"] == "lm":
        from repro.launch.train import build_lm_fl

        lm_config(cfg)
        _, server, clients, eval_fn = build_lm_fl(
            cfg["arch"], smoke=bool(cfg.get("smoke")), seed=fleet,
            **traffic["fl"])
        return clients, eval_fn, server.cfg
    raise ValueError(f"unknown builder {cfg['builder']!r}")
