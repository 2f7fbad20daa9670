"""fedml_tpu — a TPU-native federated learning + MLOps framework.

Capability parity with FedML (reference: ``/root/reference``, v0.8.18b9),
re-designed for TPU from the ground up: JAX/XLA/Pallas for compute, device
meshes + XLA collectives (ICI/DCN) for scale, functional pytree state
everywhere, and a deterministic in-process transport for testable federation
protocols.

Public surface parity with ``python/fedml/__init__.py``:
    fedml_tpu.init(args) / run_simulation() / FedMLRunner
    fedml_tpu.data.load / fedml_tpu.models.create / fedml_tpu.device.get_device
"""
from __future__ import annotations

import logging
import os
import random
from typing import Any, Optional

import numpy as np

__version__ = "0.1.0"

from fedml_tpu import constants  # noqa: E402
from fedml_tpu.arguments import (  # noqa: E402
    Arguments,
    load_arguments,
    load_arguments_from_dict,
)
from fedml_tpu.runner import FedMLRunner  # noqa: E402

_global_training_type: Optional[str] = None
_global_comm_backend: Optional[str] = None


def init(args: Optional[Arguments] = None, check_env: bool = True) -> Arguments:
    """Initialize the framework — parity with ``fedml.init()``
    (``python/fedml/__init__.py:64``): load args, seed RNGs, init the
    trust-stack singletons and the mlops sink, dispatch per training type.
    """
    global _global_training_type, _global_comm_backend
    if args is None:
        args = load_arguments(_global_training_type, _global_comm_backend)
    _global_training_type = str(getattr(args, "training_type", "simulation"))
    _global_comm_backend = str(getattr(args, "backend", ""))

    seed = int(getattr(args, "random_seed", 0))
    random.seed(seed)
    np.random.seed(seed)

    # multi-host slice init must precede any backend use (parity: the
    # reference's torchrun env parsing at __init__.py:353-360)
    from fedml_tpu.parallel.multihost import maybe_initialize_multihost

    maybe_initialize_multihost(args)
    # persistent XLA cache at JAX_COMPILATION_CACHE_DIR or the fixed
    # in-checkout default — before the first compile of the run
    from fedml_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    # per-silo override yamls (parity: _update_client_specific_args /
    # hierarchical server/client_silo config paths)
    from fedml_tpu.arguments import update_client_specific_args

    update_client_specific_args(args)

    from fedml_tpu.core.dp.fedml_differential_privacy import FedMLDifferentialPrivacy
    from fedml_tpu.core.fhe.fhe_agg import FedMLFHE
    from fedml_tpu.core.mlops import metrics as mlops_metrics
    from fedml_tpu.core.security.attacker import FedMLAttacker
    from fedml_tpu.core.security.defender import FedMLDefender

    FedMLAttacker.get_instance().init(args)
    FedMLDefender.get_instance().init(args)
    FedMLDifferentialPrivacy.get_instance().init(args)
    FedMLFHE.get_instance().init(args)
    mlops_metrics.init(args)

    _update_client_id_list(args)
    return args


def _update_client_id_list(args: Arguments) -> None:
    """Parity with ``__init__.py:409``: materialize client_id_list."""
    if not hasattr(args, "client_id_list") or args.client_id_list in (None, "[]", ""):
        total = int(getattr(args, "client_num_in_total", 0) or 0)
        args.client_id_list = list(range(1, total + 1))


# ---- one-call launchers (parity: python/fedml/launch_*.py) ----------------

def run_simulation(backend: str = constants.FEDML_SIMULATION_TYPE_SP):
    """Parity with ``fedml.run_simulation()`` (``launch_simulation.py:9``)."""
    from fedml_tpu import data as data_mod
    from fedml_tpu import device as device_mod
    from fedml_tpu import models as models_mod

    global _global_training_type, _global_comm_backend
    _global_training_type = constants.FEDML_TRAINING_PLATFORM_SIMULATION
    _global_comm_backend = backend
    args = load_arguments(_global_training_type, _global_comm_backend)
    args = init(args)
    device = device_mod.get_device(args)
    dataset = data_mod.load_federated(args)
    model = models_mod.create(args, dataset.class_num)
    runner = FedMLRunner(args, device, dataset, model)
    return runner.run()


def run_cross_silo_server():
    return _run_cross_silo(constants.ROLE_SERVER)


def run_cross_silo_client():
    return _run_cross_silo(constants.ROLE_CLIENT)


def run_hierarchical_cross_silo_server():
    """Hierarchical cross-silo (reference ``run_hierarchical_cross_silo``):
    every silo is a (multi-host) device mesh; scenario drives the per-silo
    config-path overrides."""
    return _run_cross_silo(constants.ROLE_SERVER, scenario="hierarchical")


def run_hierarchical_cross_silo_client():
    return _run_cross_silo(constants.ROLE_CLIENT, scenario="hierarchical")


def run_cross_device_server():
    """Cross-device ("BeeHive") server launcher.

    Parity: ``fedml.run_mnn_server`` (``launch_cross_device.py``) — the
    reference boots the MNN-file server for mobile clients; here the
    server is the cross-silo FSM over the federation transport and the
    device clients run ``python -m fedml_tpu.cross_device.client``.
    """
    from fedml_tpu import data as data_mod
    from fedml_tpu import device as device_mod
    from fedml_tpu import models as models_mod

    global _global_training_type
    _global_training_type = constants.FEDML_TRAINING_PLATFORM_CROSS_DEVICE
    args = load_arguments(_global_training_type, None)
    args.role = constants.ROLE_SERVER
    args.rank = 0
    args.training_type = _global_training_type
    args = init(args)
    device = device_mod.get_device(args)
    dataset = data_mod.load_federated(args)
    model = models_mod.create(args, dataset.class_num)
    return FedMLRunner(args, device, dataset, model).run()


run_mnn_server = run_cross_device_server  # reference launcher name


def run_cross_cloud_server():
    """Parity: ``_init_cross_cloud`` (ref ``__init__.py:392``) server role."""
    return _run_cross_silo(constants.ROLE_SERVER,
                           constants.FEDML_TRAINING_PLATFORM_CROSS_CLOUD)


def run_cross_cloud_client():
    return _run_cross_silo(constants.ROLE_CLIENT,
                           constants.FEDML_TRAINING_PLATFORM_CROSS_CLOUD)


def _run_cross_silo(role: str, training_type: Optional[str] = None,
                    scenario: Optional[str] = None):
    from fedml_tpu import data as data_mod
    from fedml_tpu import device as device_mod
    from fedml_tpu import models as models_mod

    global _global_training_type
    _global_training_type = (training_type
                             or constants.FEDML_TRAINING_PLATFORM_CROSS_SILO)
    args = load_arguments(_global_training_type, None)
    args.role = role
    if training_type is not None:  # cross-cloud launcher overrides the yaml
        args.training_type = training_type
    if scenario is not None:
        args.scenario = scenario
    args = init(args)
    device = device_mod.get_device(args)
    dataset = data_mod.load_federated(args)
    model = models_mod.create(args, dataset.class_num)
    return FedMLRunner(args, device, dataset, model).run()


__all__ = [
    "Arguments",
    "FedMLRunner",
    "__version__",
    "constants",
    "init",
    "load_arguments",
    "load_arguments_from_dict",
    "run_simulation",
    "run_cross_cloud_client",
    "run_cross_cloud_server",
    "run_cross_device_server",
    "run_cross_silo_client",
    "run_cross_silo_server",
    "run_hierarchical_cross_silo_client",
    "run_hierarchical_cross_silo_server",
    "run_mnn_server",
]
