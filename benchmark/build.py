"""Build one arm of a cell from the repo's own config tree.

This is ``train.py``'s wiring between "configs loaded" and "first step",
without checkpoints, evaluation or writers: ``Config.update_from_modules``
on the modules the configuration file lists, then ``make_mesh`` ->
``make_flat_setup`` -> ``make_flat_state`` -> ``shard_state`` ->
``build_train_step(..., donate=True, flat=setup)``. No private step. The
one difference is on the harness's side of the line: ``model.init`` and
``make_flat_state`` run inside ONE jitted call whose outputs already carry
``shard_state``'s shardings, so that set-up issues one program and not the
few hundred eager ones ``train.py`` does (PERF.md, set-up).
"""

import contextlib
import functools
import inspect
import os
from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from benchmark import inputs
from benchmark.cells import DATA_KINDS, ROOT, Cell, CellError


class Arm(NamedTuple):
    name: str
    dist: Any                 # DistributedOptimizer
    setup: Any                # FlatSetup (layout, stats_layout, engine)
    mesh: Any
    world: int
    dataset: Dict[str, Any]   # the file's block of that name + built sizes
    recipe: Dict[str, Any]    # the optimizer as configured, for model_check
    init: Callable            # jitted: PRNGKey -> TrainState, sharded
    step: Callable            # the program's jitted train step
    k_loop: Optional[Callable]  # loop 'scan': k steps in one dispatch


def _apply_overrides(overrides):
    from dgc_tpu.utils.config import Config
    opts = []
    for key, value in overrides.items():
        opts += ["--" + key, value if isinstance(value, str) else repr(value)]
    Config.update_from_arguments(*opts)


def _narrow_model_dtype(model):
    """``train.py::_narrow_model_dtype``: the model's sub-4-byte compute
    dtype, if any (``configs/bf16.py``)."""
    dt = getattr(model, "dtype", None)
    if dt is not None and jnp.dtype(dt).itemsize < 4:
        return dt
    return None


def _keywords(node, *names) -> Dict[str, Any]:
    """What the callable of config node ``node`` is called with for
    ``names``: the node's own value, else the callable's default."""
    defaults = inspect.signature(node.callable).parameters
    return {n: node.get(n, defaults[n].default) for n in names}


def matmul_precision(cell: Cell):
    """The context everything of the cell's program is traced and called
    in: the configuration file's ``matmul_precision``, or nothing."""
    name = cell.config["matmul_precision"]
    if name is None:
        return contextlib.nullcontext()
    return jax.default_matmul_precision(name)


def make_mesh(cell: Cell, devices=None):
    from dgc_tpu.parallel import make_mesh as _make_mesh
    if devices is None:
        devices = jax.devices()
    if len(devices) < cell.chips:
        raise SystemExit(
            f"benchmark: workload '{cell.name}' needs {cell.chips} chip(s), "
            f"JAX reports {len(devices)}")
    return _make_mesh(devices=list(devices)[:cell.chips])


def build_arm(cell: Cell, arm: str, mesh) -> Arm:
    from dgc_tpu.compression.flat import ParamLayout
    from dgc_tpu.data import num_steps_per_epoch
    from dgc_tpu.optim import DistributedOptimizer
    from dgc_tpu.training import (build_train_step, make_flat_setup,
                                  make_flat_state, make_lr_schedule,
                                  state_specs)
    from dgc_tpu.utils.config import Config, configs
    from dgc_tpu.utils.pytree import named_flatten

    cfg, traffic = cell.config, cell.traffic
    dgc = arm == "dgc"
    modules = (cfg["modules"] + (cfg["dgc_modules"] if dgc else [])
               + traffic["modules"]
               + (traffic["dgc_modules"] if dgc else []))
    Config.reset()
    Config.update_from_modules(*(os.path.join(ROOT, m) for m in modules))
    _apply_overrides(cfg["overrides"])
    configs.train.batch_size = traffic["per_chip_batch"]
    if dgc and traffic["compress_ratio"] is not None:
        configs.train.compression.compress_ratio = traffic["compress_ratio"]
    if bool(configs.train.dgc) != dgc:
        raise CellError(
            f"config '{cell.config_name}', arm '{arm}': the modules "
            f"{modules} leave configs.train.dgc = {configs.train.dgc}")

    world = mesh.devices.size
    axis = mesh.axis_names[0]
    nbps = configs.train.get("num_batches_per_step", 1)
    model = configs.model()
    kind = cfg["dataset"]["kind"]
    built = {key: configs.dataset.get(key) for key in DATA_KINDS[kind]}
    # what the generator makes: the file's block with the built sizes
    dataset = {**cfg["dataset"], **built}

    def init_variables(key):
        return model.init(key, inputs.sample_input(dataset), train=True)

    variables = jax.eval_shape(init_variables, jax.random.PRNGKey(0))
    params = variables["params"]
    named_params, _ = named_flatten(params)
    built["num_parameters"] = sum(int(p.size) for p in named_params.values())
    sizes = cfg["sizes"]
    for key, got in built.items():
        if sizes[key] != got:
            raise CellError(
                f"config '{cell.config_name}': the file states {key} = "
                f"{sizes[key]}, the built model has {got}")

    # LR exactly as train.py derives it (scaled by nbps * world, warm-up,
    # the config's decay); the epoch length is the configuration file's
    global_batch = world * nbps * traffic["per_chip_batch"]
    steps_per_epoch = num_steps_per_epoch(dataset["epoch_examples"],
                                          global_batch, drop_last=nbps > 1)
    decay = (configs.train.scheduler()
             if configs.train.get("scheduler") is not None else None)
    lr_schedule = make_lr_schedule(
        scaled_lr=configs.train.optimizer.lr * nbps * world,
        world_size=world, num_steps_per_epoch=steps_per_epoch,
        warmup_lr_epochs=configs.train.warmup_lr_epochs, decay=decay,
        schedule_lr_per_epoch=configs.train.schedule_lr_per_epoch)

    if dgc:
        memory = configs.train.compression.memory()
        compression = configs.train.compression(memory=memory)
        compression.initialize(
            (n, p) for n, p in named_params.items() if p.ndim > 1)
        compression.warmup_compress_ratio(0)      # train.py, epoch 0
    else:
        compression = configs.train.compression()

    wd_mask = None
    if configs.train.get("optimize_bn_separately", False):
        layout = ParamLayout.for_compressor(params, compression)
        wd_mask = layout.mask_vector(lambda n: "BatchNorm" not in n)
    optimizer = configs.train.optimizer(lr=lr_schedule,
                                        weight_decay_mask=wd_mask)
    recipe = {"lr": lr_schedule,
              "undecayed": "BatchNorm" if wd_mask is not None else None,
              **_keywords(configs.train.optimizer, "momentum", "dampening",
                          "weight_decay", "nesterov")}
    dist = DistributedOptimizer(optimizer, compression, axis_name=axis,
                                world_size=world)
    setup = make_flat_setup(variables, dist)

    def flat_state(v):
        return make_flat_state(v, dist, setup, world)

    def make_state(key):
        return flat_state(init_variables(key))

    abstract_state = jax.eval_shape(flat_state, variables)
    specs = state_specs(abstract_state, axis, dist.per_worker_opt_state)
    shardings = jax.tree.map(lambda _, sp: NamedSharding(mesh, sp),
                             abstract_state, specs)
    init = jax.jit(make_state, out_shardings=shardings)

    scan = traffic["loop"] == "scan"
    step = build_train_step(model.apply, dist, mesh,
                            num_batches_per_step=nbps, use_dropout=True,
                            donate=not scan, flat=setup,
                            model_dtype=_narrow_model_dtype(model))
    k_loop = _make_k_loop(step, traffic["k"]) if scan else None
    return Arm(name=arm, dist=dist, setup=setup, mesh=mesh, world=world,
               dataset=dataset, recipe=recipe, init=init, step=step,
               k_loop=k_loop)


def _make_k_loop(step, k: int):
    """``bench.py::_make_k_loop``: k train steps inside one jitted
    ``lax.scan`` with the state donated, so that host dispatch latency
    stays out of a sub-millisecond step. Step i reads resident batch
    ``i mod n`` of the stacked batches."""

    @functools.partial(jax.jit, donate_argnums=(0,))
    def k_loop(state, images, labels, key):
        n = images.shape[0]

        def body(s, xs):
            i, ki = xs
            s2, m = step(s, images[i % n], labels[i % n], ki)
            return s2, m["loss"]

        return jax.lax.scan(body, state,
                            (jnp.arange(k), jax.random.split(key, k)))

    return k_loop


def init_state(arm: Arm, seed: int):
    """The arm's initial state on the mesh: weights from ``model.init`` on
    ``PRNGKey(seed)``, made on the device in one jitted call; then the
    program's ``shard_state`` (which finds every leaf already in place)."""
    from dgc_tpu.training import shard_state
    state = arm.init(jax.random.PRNGKey(seed))
    return shard_state(state, arm.mesh, arm.mesh.axis_names[0],
                       dist_opt=arm.dist)
