"""The system under test, as the benchmark drives it.

The only module of the benchmark that imports the program (`src/repro`).
It builds the configuration's model through `repro.models.build_model`
(architecture: `arch_config`, the named `repro.configs` entry with every
field the configuration file names put in its place), under the configured
preset and mode, and the jitted training step through
`repro.launch.train.make_train_step` (dp == 1) or `make_sharded_train_step`
(dp > 1, over the integer gradient wire).  Parameters come from the
benchmark; the program only checks that their tree is the one it builds.

The step is lowered under JAX's default matmul precision set to the
configuration's `float32_precision`: the program gives its float32 layers
(a ResNet's first conv and last FC) no precision of their own, so on a TPU
they would run in one bfloat16 pass.  The setting reaches every conv and
dot of the step that names no precision; the quantized ones see only values
on grids of at most 8 significant bits, so their results are the same in
either precision.  The batch the step takes is the traffic's (`traffic.py`),
and the work it is held to is counted by the family's work file,
`work/<family>.py`.
"""
from __future__ import annotations

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src")


def check_present():
    if not os.path.isdir(os.path.join(SRC, "repro")):
        raise SystemExit(f"bench: the system under test is missing ({SRC})")


def _import():
    check_present()
    if SRC not in sys.path:
        sys.path.insert(0, SRC)


# Fields of the named architecture that a configuration file never replaces.
KEPT = ("name", "family", "source")


def _tuples(v):
    return tuple(_tuples(x) for x in v) if isinstance(v, list) else v


def arch_config(config):
    """The program's `ArchConfig` for a configuration file: the architecture
    that `config["arch"]` names, with every `ArchConfig` field the file
    names (but `KEPT`) in its place, lists as tuples.  A file whose `family`
    is not the architecture's is refused."""
    _import()
    from repro.configs import get as get_arch

    base = get_arch(config["arch"])
    if config["family"] != base.family:
        raise SystemExit(f"bench: configuration {config['name']!r} is of "
                         f"family {config['family']!r}, its arch "
                         f"{config['arch']!r} of {base.family!r}")
    fields = {f.name for f in dataclasses.fields(base)} - set(KEPT)
    return base.replace(**{k: _tuples(v) for k, v in config.items()
                           if k in fields})


class Program:
    """Model, optimizer state and compiled step of one cell."""

    def __init__(self, config, cell):
        _import()
        from repro.core.qconfig import preset
        from repro.kernels import ops
        from repro.models import build_model

        self.ops = ops
        self.acfg = arch_config(config)
        self.qcfg = preset(config["preset"], config["mode"])
        self.model = build_model(self.acfg, self.qcfg)
        self.dp, self.n_shards = cell["dp"], cell["n_shards"]
        self.opt_cfg = config["optimizer"]
        self.precision = config["float32_precision"]
        self.mesh = self.specs = None
        if self.dp > 1 or self.n_shards > 1:
            from repro.launch.mesh import make_cpu_mesh
            self.mesh = make_cpu_mesh(self.dp, 1)

    def check_tree(self, params):
        want = jax.eval_shape(self.model.init, jax.random.PRNGKey(0))
        got = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                           params)
        if jax.tree.structure(want) != jax.tree.structure(got) or any(
                (a.shape, a.dtype) != (b.shape, b.dtype)
                for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
            raise SystemExit("bench: the program's parameter tree differs "
                             "from the benchmark's")

    def batch_sharding(self):
        if self.mesh is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(self.mesh, P("data"))

    def build(self, params, batch):
        """The step compiled once, here, for this batch shape; then
        `state(params)` gives the (params, opt state) it takes."""
        from repro.launch.train import make_sharded_train_step, \
            make_train_step

        labels = self.model.labels(params)
        lr, mom = self.opt_cfg["lr"], self.opt_cfg["mom"]
        if self.mesh is None:
            fn = make_train_step(self.model, self.qcfg, labels, lr=lr,
                                 mom=mom)
        else:
            fn, self.specs = make_sharded_train_step(
                self.model, self.qcfg, labels, self.mesh, params, lr=lr,
                mom=mom, n_shards=self.n_shards, wire_codec="auto")
        params, opt = self.state(params)
        with jax.default_matmul_precision(self.precision):
            lowered = jax.jit(fn, donate_argnums=(0, 1)).lower(
                params, opt, batch, jnp.int32(0))
        return lowered.compile()

    def state(self, params):
        """(params, fresh optimizer state), laid out for the step."""
        from repro.optim import init_momentum
        opt = init_momentum(params)
        if self.mesh is None:
            return params, opt
        from repro.launch import shard as S
        return (self.place(params),
                S.shard_arrays(self.mesh, opt, self.specs["opt"]))

    def place(self, params):
        """Params laid out as `build` lays them (for a second copy)."""
        if self.mesh is None:
            return params
        from repro.launch import shard as S
        return S.shard_arrays(self.mesh, params, self.specs["params"])

    def oracle_calls(self) -> int:
        """Traced calls that took an XLA oracle in place of a kernel on a
        TPU, as the program's dispatch counts them."""
        return int(sum(self.ops.dispatch_report()["oracle_on_tpu"].values()))
